"""Worker for test_distributed_multiproc: one process of a 2-process
``jax.distributed`` CPU cluster (4 virtual devices each → 8 global).

Spawned with a CPU-only environment (``utils/hostenv``) so jax
initializes a plain CPU backend; cross-process collectives ride Gloo. Prints one ``RESULT {...}``
JSON line the parent asserts on.
"""

import json
import sys


def build_image_frame(num_rows: int, num_partitions: int):
    """A deterministic image frame every process (and the test's
    reference run) can rebuild identically: row ``i`` carries a seeded
    32x32 uint8 image and key column ``x = i``."""
    import numpy as np
    import pyarrow as pa

    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.image import imageIO

    structs = []
    for i in range(num_rows):
        arr = np.random.default_rng(1000 + i).integers(
            0, 255, (32, 32, 3), dtype=np.uint8)
        structs.append(imageIO.imageArrayToStruct(arr, origin=str(i)))
    batch = imageIO.structsToBatch(
        structs, extra_columns={"x": pa.array(list(range(num_rows)))})
    return DataFrame.from_table(
        pa.Table.from_batches([batch]), num_partitions)


def featurize_rows(df):
    """(x, sum(features)) per row through DeepImageFeaturizer(TestNet)
    on the local-device mesh — multi-host DP inference is exactly
    'every host runs its shard on its own chips', no collectives."""
    import numpy as np

    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    out = DeepImageFeaturizer(modelName="TestNet", inputCol="image",
                              outputCol="f", useMesh=True).transform(df)
    table = out.collect()
    xs = table.column("x").to_pylist()
    sums = [float(np.sum(v)) for v in table.column("f").to_pylist()]
    return sorted(zip(xs, sums))


def main() -> None:
    pid = int(sys.argv[1])
    port = sys.argv[2]
    num_partitions = int(sys.argv[3])

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkdl_tpu.parallel import distributed as dist
    from sparkdl_tpu.parallel.mesh import DATA_AXIS, MeshSpec

    # Explicit join (the TPU-pod path auto-detects; tests pass params).
    dist.initialize(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=2, process_id=pid)
    info = dist.host_info()

    # Global-mesh psum: every process contributes its local shard of a
    # global ("data",)-sharded array; the jitted sum needs a
    # cross-process collective (Gloo here, ICI/DCN on a pod).
    mesh = dist.global_mesh(MeshSpec(data=-1, model=1))
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    local = np.arange(info.local_device_count, dtype=np.float64) + 10 * pid
    garr = jax.make_array_from_process_local_data(
        sharding, local, (info.global_device_count,))
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(garr)

    # host_shard_dataframe end-to-end: each host materializes only its
    # own partitions of the same logical frame.
    from sparkdl_tpu.data.frame import DataFrame
    rows = [{"x": i} for i in range(4 * num_partitions - 1)]
    df = DataFrame.from_pylist(rows, num_partitions=num_partitions)
    mine = dist.host_shard_dataframe(df)
    xs = sorted(r["x"] for r in mine.collect_rows())

    # one full DP train step over the GLOBAL mesh: per-process local
    # batch shards assemble into one global batch; the gradient
    # all-reduce crosses processes (both must see the same loss)
    import optax

    from sparkdl_tpu.models.testnet import TestNet
    from sparkdl_tpu.models.zoo import getKerasApplicationModel
    from sparkdl_tpu.parallel.train import (
        create_train_state,
        make_train_step,
        shard_train_step,
    )

    spec = getKerasApplicationModel("TestNet")
    module = TestNet()
    x0 = spec.preprocess(jnp.zeros((1, 32, 32, 3), jnp.uint8))
    variables = module.init(jax.random.PRNGKey(0), x0)
    state = create_train_state(module, variables, optax.sgd(1e-2, 0.9))
    train_step = make_train_step(module, spec.preprocess,
                                 num_classes=spec.num_classes)
    jitted, state = shard_train_step(train_step, mesh, state)

    per_proc = 2 * info.local_device_count
    brng = np.random.default_rng(pid)
    imgs = brng.integers(0, 255, (per_proc, 32, 32, 3), np.uint8)
    labels = ((np.arange(per_proc) + pid)
              % spec.num_classes).astype(np.int32)
    gb = 2 * info.global_device_count
    batch = {
        "image": jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(DATA_AXIS)), imgs, (gb, 32, 32, 3)),
        "label": jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(DATA_AXIS)), labels, (gb,)),
    }
    state, metrics = jitted(state, batch)
    train_loss = float(metrics["loss"])

    # multi-host DP inference: featurize ONLY this host's shard of a
    # shared logical frame on this host's local mesh
    img_df = build_image_frame(4 * num_partitions - 1, num_partitions)
    feats = featurize_rows(dist.host_shard_dataframe(img_df))

    print("RESULT " + json.dumps({
        "pid": pid,
        "process_count": info.process_count,
        "local_devices": info.local_device_count,
        "global_devices": info.global_device_count,
        "shard_indices": dist.host_shard_indices(num_partitions),
        "psum_total": float(total),
        "rows": xs,
        "train_loss": train_loss,
        "features": feats,
    }), flush=True)


if __name__ == "__main__":
    main()
