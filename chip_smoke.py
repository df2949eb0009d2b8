#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of InceptionV3 (299x299x3 uint8 in, 2048-d
out, device batch 128; weights are seeded-random — the summary says
so), and checks what comes out by the repo's own means. Legs, in order:

* ``transform`` — JPEG files -> ``readImagesPacked(yuv420)`` ->
  ``TensorTransformer(deviceResizeModel(InceptionV3))`` -> collect; the
  named ``DeepImageFeaturizer`` stage; the same transform again with
  decode on a process pool (the children must stay off the chip);
* ``serve`` — ``ModelServer`` answering concurrent requests, equal to
  the offline ``BatchRunner`` row for row, with no unexpected retrace;
* ``fit`` — ``KerasImageFileEstimator`` taking a few steps on a small
  Keras CNN: the loss falls and no donated buffer goes unused;
* ``parity`` — ``DeepImagePredictor(TestNet)``, the one model with
  committed trained weights, hits top-1 on its synthetic dataset;
* ``kernel`` — the Pallas fused resize compiled for the chip
  (``interpret=False``) against the einsum path;
* ``mesh`` — only with more than one local device: the transform and
  a few estimator steps over all of them.

It refuses to run unless JAX's first device is a TPU, lets every leg's
exception propagate, checks afterwards that no probe-and-degrade
counter moved, and prints two JSON lines to stdout: the summary
(``summary={...}``: per-leg wall times, compile-cache hits and misses,
the shim's source hash, weights provenance) and, LAST, the verdict with
exactly these keys, the device as JAX reports it:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The leg functions take the model name and sizes as arguments so that
tests/test_chip_smoke.py can run them on the CPU with TestNet.
"""

from __future__ import annotations

import os

# keras reads its backend at import; set it before anything imports it
os.environ.setdefault("KERAS_BACKEND", "jax")

import gc
import json
import math
import shutil
import sys
import tempfile
import time
import warnings

import numpy as np

MODEL = "InceptionV3"
BATCH = 128            # the device batch
N_IMAGES = 512         # 4 runner batches
PACKED_SRC = (150, 150)

# probe-and-degrade counters: after the legs every one must read 0 — a
# degraded path finishes with the right answer, several times slower
DEGRADE_COUNTERS = (
    "sanitize.degrade_events",
    "pipeline.degrade_events", "pipeline.fallbacks",
    # a retried partition is a device error that was hidden
    "engine.retries",
)


def _column(table, name: str) -> np.ndarray:
    from sparkdl_tpu import DataFrame
    return DataFrame.from_table(table, 1).tensor(name)


def packed_model(model: str, src_hw):
    """The featurizer fed packed 4:2:0 rows, resize fused on device."""
    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.transformers.utils import deviceResizeModel
    return deviceResizeModel(getModelFunction(model, featurize=True),
                             src_hw, packedFormat="yuv420")


def packed_transform(mf, corpus: str, src_hw, batch: int, engine=None,
                     use_mesh: bool = False):
    """bench.measure_pipeline's path: files -> fused native decode/pack
    -> ship -> fused device featurize, over batch-misaligned
    partitions. Returns (collected table, the transformer)."""
    from sparkdl_tpu import TensorTransformer
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers.utils import single_io

    in_name, out_name = single_io(mf)
    df = imageIO.readImagesPacked(corpus, src_hw, numPartitions=8,
                                  packedFormat="yuv420", engine=engine)
    t = TensorTransformer(modelFunction=mf,
                          inputMapping={"image": in_name},
                          outputMapping={out_name: "features"},
                          batchSize=batch, useMesh=use_mesh)
    return t.transform(df).collect(), t


def _check_features(feats: np.ndarray, n: int) -> None:
    assert feats.ndim == 2 and feats.shape[0] == n, feats.shape
    assert np.isfinite(feats).all(), "non-finite features"


def leg_transform(model: str, batch: int, n_images: int, src_hw,
                  workdir: str) -> dict:
    from sparkdl_tpu import DeepImageFeaturizer, readImages
    from sparkdl_tpu.data import pipeline as host_pipeline
    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.models.zoo import getKerasApplicationModel
    from sparkdl_tpu.utils.synth import write_textured_jpegs

    corpus = os.path.join(workdir, "corpus")
    paths = write_textured_jpegs(corpus, n_images)
    mf = packed_model(model, src_hw)
    feature_dim = getKerasApplicationModel(model).feature_dim

    table, t = packed_transform(mf, corpus, src_hw, batch)
    feats = _column(table, "features")
    _check_features(feats, n_images)
    assert feats.shape[1] == feature_dim, feats.shape
    assert table.column("filePath").to_pylist() == paths, \
        "row order not kept"
    assert t.metrics.batches >= math.ceil(n_images / batch), \
        t.metrics.batches
    distinct = len({row.tobytes() for row in feats})
    assert distinct >= 0.9 * n_images, \
        f"features do not vary across images ({distinct} distinct rows)"

    # the named stage over the first device batch of the same files
    first = os.path.join(workdir, "first")
    os.makedirs(first)
    for p in paths[:batch]:
        shutil.copy(p, first)
    named = DeepImageFeaturizer(
        modelName=model, inputCol="image", outputCol="features",
        batchSize=batch).transform(readImages(first)).tensor("features")
    _check_features(named, min(batch, n_images))
    assert named.shape[1] == feature_dim, named.shape

    # decode on a process pool: the children import modules that import
    # jax, and must never initialise a backend — this process holds the
    # chip. Bit-identical to the serial pass, and really pooled.
    engine = LocalEngine(pipeline_workers=2, pipeline_mode="process")
    try:
        pooled_table, _ = packed_transform(mf, corpus, src_hw, batch,
                                           engine=engine)
    finally:
        engine.shutdown()
    pooled = host_pipeline.state()
    assert pooled.get("mode") == "process" and pooled.get("workers") == 2, \
        f"the pooled pass did not run on 2 processes: {pooled}"
    assert np.array_equal(_column(pooled_table, "features"), feats), \
        "pooled pass differs from the serial pass"

    return {"mf": mf, "corpus": corpus, "features": feats,
            "packed": _column(table, "image")}


def leg_serve(mf, batch: int, packed: np.ndarray) -> dict:
    """32 requests of 1..64 rows from 4 threads against the offline
    runner's output on the same arrays."""
    from concurrent.futures import ThreadPoolExecutor

    from sparkdl_tpu.obs.compile_log import compile_log
    from sparkdl_tpu.runtime.runner import BatchRunner
    from sparkdl_tpu.serve import ModelServer
    from sparkdl_tpu.transformers.utils import single_io

    in_name, out_name = single_io(mf)
    runner = BatchRunner(mf, batch_size=batch)
    want = runner.run({in_name: packed})[out_name]

    rng = np.random.default_rng(11)
    sizes = rng.integers(1, min(64, len(packed)) + 1, size=32)
    starts = rng.integers(0, len(packed) - sizes + 1)
    log = compile_log()
    log.arm()
    retraces0 = log.unexpected_retraces
    with ModelServer() as server, ThreadPoolExecutor(4) as clients:
        server.register("smoke", mf, batch_size=batch)
        assert server.warmup() == {"smoke": True}

        def request(i: int) -> np.ndarray:
            rows = packed[starts[i]:starts[i] + sizes[i]]
            return server.submit({in_name: rows}).result(
                timeout=300)[out_name]

        # map() re-raises a client's exception when its result is read
        results = list(clients.map(request, range(len(sizes))))
        rejected = server.metrics.rejections
    worst = 0.0
    for i, got in enumerate(results):
        ref = want[starts[i]:starts[i] + sizes[i]]
        assert got.shape == ref.shape, (got.shape, ref.shape)
        worst = max(worst, float(np.abs(got - ref).max()))
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    assert log.unexpected_retraces == retraces0, log.state()["last_event"]
    assert rejected == 0
    return {"max_inflight": runner.max_inflight, "max_abs_diff": worst}


def _load_png(uri: str) -> np.ndarray:
    from PIL import Image
    return np.asarray(Image.open(uri).convert("RGB"),
                      dtype=np.float32) / 255.0 - 0.5


def leg_fit(workdir: str, use_mesh: bool = False) -> dict:
    """A few estimator steps on a small Keras CNN over brightness-
    labelled images."""
    import keras
    from PIL import Image

    from sparkdl_tpu import DataFrame, KerasImageFileEstimator

    side, n, step_batch, epochs = 32, 64, 16, 4
    d = tempfile.mkdtemp(prefix="fit_", dir=workdir)
    rng = np.random.default_rng(5)
    rows = []
    for i in range(n):
        label = i % 2
        arr = np.clip(rng.normal(60 if label == 0 else 190, 20,
                                 (side, side, 3)), 0, 255).astype(np.uint8)
        p = os.path.join(d, f"i{i:03d}.png")
        Image.fromarray(arr, "RGB").save(p)
        rows.append({"uri": p, "label": label})
    keras.utils.set_random_seed(123)
    cnn = keras.Sequential([
        keras.layers.Input((side, side, 3)),
        keras.layers.Conv2D(8, 3, strides=2, activation="relu"),
        keras.layers.Conv2D(16, 3, strides=2, activation="relu"),
        keras.layers.GlobalAveragePooling2D(),
        keras.layers.Dense(2, activation="softmax"),
    ])
    model_file = os.path.join(d, "cnn.keras")
    cnn.save(model_file)

    est = KerasImageFileEstimator(
        inputCol="uri", outputCol="prediction", labelCol="label",
        modelFile=model_file, imageLoader=_load_png,
        kerasOptimizer="adam", kerasLoss="categorical_crossentropy",
        kerasFitParams={"epochs": epochs, "batch_size": step_batch,
                        "learning_rate": 0.01, "seed": 1},
        batchSize=step_batch, useMesh=use_mesh)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        fitted = est.fit(DataFrame.from_pylist(rows, num_partitions=4))
    losses = list(fitted.history)
    assert len(losses) == epochs and np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    undonated = [str(w.message) for w in caught
                 if "donated buffers were not usable" in str(w.message)]
    assert not undonated, undonated[0]
    return {"losses": [round(float(v), 4) for v in losses],
            "steps": epochs * (n // step_batch)}


def leg_parity(workdir: str) -> dict:
    """The numerical check that needs no second backend: the committed
    trained TestNet must put the true class first."""
    from PIL import Image

    from sparkdl_tpu import DeepImagePredictor, readImages
    from sparkdl_tpu.models.testnet import synthetic_testnet_dataset

    d = tempfile.mkdtemp(prefix="parity_", dir=workdir)
    imgs, labels = synthetic_testnet_dataset(48, seed=7)
    for i, arr in enumerate(imgs):
        Image.fromarray(arr, "RGB").save(os.path.join(d, f"e{i:02d}.png"))
    table = DeepImagePredictor(
        modelName="TestNet", inputCol="image", outputCol="preds",
        decodePredictions=True, topK=3).transform(
            readImages(d, numPartitions=3)).collect()
    order = [int(p[-6:-4]) for p in table.column("filePath").to_pylist()]
    rows = table.column("preds").to_pylist()
    hits = sum(row[0]["class"] == f"proto_{labels[i]}"
               for row, i in zip(rows, order))
    top1 = hits / len(rows)
    assert top1 >= 0.95, f"TestNet top-1 {top1} < 0.95"
    return {"top1": round(top1, 4)}


def leg_kernel(batch: int, src_sides, out_side: int,
               interpret: bool = False) -> dict:
    """The Pallas fused resize against the einsum path, to the
    tolerance tests/test_ops.py holds the kernel to."""
    import jax

    from sparkdl_tpu.ops.infeed import fused_resize_normalize

    rng = np.random.default_rng(2)
    worst = {}
    for side in src_sides:
        x = rng.integers(0, 255, (batch, side, side, 3), dtype=np.uint8)

        def run(use_pallas: bool):
            return np.asarray(jax.jit(
                lambda a: fused_resize_normalize(
                    a, (out_side, out_side), scale=1 / 127.5,
                    offset=-1.0, use_pallas=use_pallas,
                    interpret=interpret))(x))

        got, ref = run(True), run(False)
        assert got.shape == (batch, out_side, out_side, 3), got.shape
        np.testing.assert_allclose(got, ref, atol=1e-5)
        worst[f"{side}->{out_side}"] = float(np.abs(got - ref).max())
    return {"max_abs_diff": worst}


def leg_mesh(mf, corpus: str, src_hw, batch: int, n_images: int,
             one_device_features: np.ndarray, workdir: str) -> dict:
    """The transform and a few estimator steps over ALL local devices,
    asserting the device count each actually used."""
    import jax

    from sparkdl_tpu.obs.compile_log import compile_log
    from sparkdl_tpu.parallel.mesh import DATA_AXIS, make_mesh

    devices = jax.local_devices()
    n_dev = len(devices)
    assert n_dev > 1, "the mesh leg needs more than one device"
    # the mesh ShardedBatchRunner builds for itself (equal meshes share
    # the model's cached replicated placement)
    mesh = make_mesh(devices=devices)
    assert mesh.shape[DATA_AXIS] == n_dev, dict(mesh.shape)
    gc.collect()
    before = [d.memory_stats() for d in devices]

    table, t = packed_transform(mf, corpus, src_hw, batch, use_mesh=True)
    feats = _column(table, "features")
    _check_features(feats, n_images)
    # batchSize is per chip under useMesh: a global batch spans them all
    assert t.metrics.batches == math.ceil(n_images / (batch * n_dev)), \
        (t.metrics.batches, n_images, batch, n_dev)
    scale = float(np.abs(one_device_features).max())
    drift = float(np.abs(feats - one_device_features).max())
    assert drift <= 2e-2 * scale, \
        f"mesh features drift {drift} from one device's (scale {scale})"

    for leaf in jax.tree_util.tree_leaves(mf.replicated_params(mesh)):
        assert leaf.sharding.device_set == set(devices), leaf.sharding
        assert leaf.is_fully_replicated
    grew = []
    for d, b in zip(devices, before):
        if b is None:       # the CPU backend reports no memory stats
            continue
        rose = d.memory_stats()["bytes_in_use"] - b["bytes_in_use"]
        assert rose > 0, f"{d} holds nothing new after the mesh transform"
        grew.append(rose)

    # gradient all-reduce: the step must have compiled against the mesh
    compile_log().arm()
    fit = leg_fit(workdir, use_mesh=True)
    step = compile_log().events_for(
        "KerasImageFileEstimator.train_step")[-1]
    assert step.kind == "sharded_jit", step.kind
    assert dict(step.config["mesh"])[DATA_AXIS] == n_dev, step.config
    return {"devices": n_dev, "feature_drift": drift,
            "bytes_in_use_rose": grew, "fit_losses": fit["losses"]}


class _CacheEvents:
    """Counts JAX's persistent-compilation-cache events. ``misses`` is
    what the run had to compile AND wrote to the cache (compiles under
    the cache's 1 s floor are never written and count nowhere)."""

    def __init__(self):
        self.hits = self.misses = 0

    def __call__(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def verdict_line(platform: str, kind: str, count: int) -> str:
    """The last stdout line: these keys and no others."""
    return json.dumps({"ok": True,
                       "device": {"platform": platform, "kind": kind,
                                  "count": count}})


def main() -> int:
    started = time.perf_counter()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {platform!r}; no leg was run", file=sys.stderr)
        return 2

    from sparkdl_tpu import native
    from sparkdl_tpu.fleet.placement import device_budgets
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.models.zoo import weights_provenance
    from sparkdl_tpu.obs import default_registry
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    from sparkdl_tpu.utils.measure import measure_link

    cache_dir = configure_compile_cache()
    cache = _CacheEvents()
    jax.monitoring.register_event_listener(cache)
    n_dev = len(jax.local_devices())
    legs: dict = {}

    def run(name: str, fn, *args, **kw):
        h0, m0 = cache.hits, cache.misses
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        wall = round(time.perf_counter() - t0, 1)
        legs[name] = {"wall_s": wall, "cache_hits": cache.hits - h0,
                      "cache_misses": cache.misses - m0}
        print(f"leg={name} ok wall_s={wall}", flush=True)
        return out

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        link = measure_link(64)
        print(f"observed link_64MB h2d_MBps={link['h2d_MBps']} "
              f"d2h_MBps={link['d2h_MBps']} host_cores={os.cpu_count()}",
              flush=True)
        tr = run("transform", leg_transform, MODEL, BATCH, N_IMAGES,
                 PACKED_SRC, workdir)
        serve = run("serve", leg_serve, tr["mf"], BATCH, tr["packed"])
        fit = run("fit", leg_fit, workdir)
        parity = run("parity", leg_parity, workdir)
        kernel = run("kernel", leg_kernel, BATCH, (150, 299), 299)
        if n_dev > 1:
            mesh = run("mesh", leg_mesh, tr["mf"], tr["corpus"],
                       PACKED_SRC, BATCH, N_IMAGES, tr["features"],
                       workdir)
        else:
            mesh = "not run: 1 device"
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    reg = default_registry()
    moved = {c: v for c in DEGRADE_COUNTERS
             if (v := reg.counter(c).value)}
    assert not moved, f"a fallback hid something: {moved}"
    shim = native.build_info()
    assert shim["source_sha"] and shim["jpeg"], \
        f"the native JPEG shim is missing: {shim}"
    assert not imageIO._warned_fused_fallback, \
        imageIO._warned_fused_fallback
    # fleet placement plans from measured budgets only where every
    # device reports its memory
    assert all(b.source == "measured" for b in device_budgets()), \
        device_budgets()

    device, count = jax.devices()[0], len(jax.devices())
    print("summary=" + json.dumps({
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": count,
        "jax": jax.__version__,
        "host_cores": os.cpu_count(),
        "wall_s": round(time.perf_counter() - started, 1),
        "legs": legs,
        "mesh": mesh,
        "max_inflight": serve["max_inflight"],
        "serve_max_abs_diff": serve["max_abs_diff"],
        "fit_losses": fit["losses"],
        "testnet_top1": parity["top1"],
        "kernel_max_abs_diff": kernel["max_abs_diff"],
        "link_64MB_MBps": link,
        "compile_cache": {"dir": cache_dir, "hits": cache.hits,
                          "misses": cache.misses},
        "native": shim,
        "weights": {MODEL: weights_provenance(MODEL),
                    "TestNet": weights_provenance("TestNet")},
        "claim": None,
    }), flush=True)
    print(verdict_line(device.platform, device.device_kind, count),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
