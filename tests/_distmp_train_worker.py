"""Worker for test_distributed_multiproc's multi-host STREAMING
estimator fit: one process of a 2-process CPU cluster training one
Keras model data-parallel over the pod-wide mesh, each host streaming
only its own partition shard."""

import json
import sys


def main() -> None:
    pid = int(sys.argv[1])
    port = sys.argv[2]
    images_dir = sys.argv[3]
    model_file = sys.argv[4]
    num_partitions = int(sys.argv[5]) if len(sys.argv) > 5 else 4
    # optional: a checkpoint dir triggers the interrupted-run scenario
    # (fit 1 epoch with checkpoints, then extend to 2 — must resume and
    # land exactly where the uninterrupted 2-epoch fit lands)
    ckpt_dir = sys.argv[6] if len(sys.argv) > 6 else None

    import numpy as np

    from sparkdl_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=2, process_id=pid)

    # persistent compile cache: the checkpoint scenario runs THREE fits
    # of the same program shapes — compile once (concurrent-safe:
    # atomic renames)
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()

    import glob
    import os

    from sparkdl_tpu.data import DataFrame
    from sparkdl_tpu.estimators import KerasImageFileEstimator

    rows = []
    for p in sorted(glob.glob(os.path.join(images_dir, "*.png"))):
        label = int(os.path.basename(p).split("_")[1].split(".")[0]) % 2
        rows.append({"uri": p, "label": label})
    df = DataFrame.from_pylist(rows, num_partitions=num_partitions)

    def loader(uri):
        from PIL import Image
        return np.asarray(Image.open(uri).convert("RGB"),
                          dtype=np.float32) / 255.0

    def make_est(epochs, checkpointDir=None, cacheDecoded=False):
        kw = dict(
            inputCol="uri", outputCol="pred", labelCol="label",
            imageLoader=loader, modelFile=model_file,
            kerasOptimizer="adam", kerasLoss="categorical_crossentropy",
            kerasFitParams={"epochs": epochs, "batch_size": 8,
                            "learning_rate": 0.05, "seed": 3},
            streaming=True, useMesh=True, cacheDecoded=cacheDecoded)
        if checkpointDir:
            kw["checkpointDir"] = checkpointDir
        return KerasImageFileEstimator(**kw)

    def digest_of(model):
        # weight digest proves every host holds identical params
        leaves = [np.asarray(v) for v in
                  model.modelFunction.params["trainable"]]
        return float(sum(np.abs(a).sum() for a in leaves))

    model = make_est(epochs=2).fit(df)

    result = {
        "pid": pid,
        "history": model.history,
        "weight_digest": digest_of(model),
        "local_partitions": dist.host_shard_dataframe(df).num_partitions,
    }

    if not ckpt_dir:
        # cacheDecoded in the multi-host path: each host spills only
        # ITS shard; epoch 2 streams the cache. Must land on the exact
        # same replicated state as the uncached fit above.
        cached = make_est(epochs=2, cacheDecoded=True).fit(df)
        result["cached_history"] = cached.history
        result["cached_digest"] = digest_of(cached)

    if ckpt_dir:
        # interrupted: 1 epoch saved, then the same config extended to
        # 2 epochs resumes from the per-host checkpoint (every host
        # agrees on the resume step over DCN) and must match the
        # uninterrupted run above bit-for-bit in history and weights
        short = make_est(epochs=1, checkpointDir=ckpt_dir).fit(df)
        resumed = make_est(epochs=2, checkpointDir=ckpt_dir).fit(df)
        result["short_history"] = short.history
        result["resumed_history"] = resumed.history
        result["resumed_digest"] = digest_of(resumed)
        # observable resume proof: a silent from-scratch retrain would
        # reproduce identical history/weights (deterministic seeds), so
        # assert the restore actually happened via resumedFrom
        result["short_resumed_from"] = short.resumedFrom
        result["resumed_from"] = resumed.resumedFrom

    print("RESULT " + json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
