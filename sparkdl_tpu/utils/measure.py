"""Shared measurement primitives for bench.py and tools/measure_transfer.py.

One home for the timing methodology: JAX dispatch is asynchronous, so
every timed region ends in a tiny DEPENDENT readback of its result. Both
the driver bench and the strategy-selection tool import from here so a
methodology fix can never apply to one and not the other.
"""

from __future__ import annotations

import time

import numpy as np


def sync_readback(x) -> float:
    """Force completion of everything ``x`` depends on via a 1-element
    dependent readback."""
    import jax.numpy as jnp
    return float(jnp.reshape(x, (-1,))[0].astype(jnp.float32))


def measure_link(n_mb: int) -> dict:
    """Host↔device bandwidth in MB/s: ``device_put`` timed against a
    dependent 1-element readback (the sum can't run before the transfer
    lands), then ``device_get`` of the resident buffer. The untimed
    first pass compiles the full-shape sum — a smaller warm-up shape
    would leave that compile inside the timed upload."""
    import jax

    x = np.random.default_rng(0).integers(
        0, 255, size=(n_mb * 1024 * 1024,), dtype=np.uint8)
    sync_readback(jax.device_put(x).sum())  # compile + warm the path
    t0 = time.perf_counter()
    d = jax.device_put(x)
    sync_readback(d.sum())
    up = time.perf_counter() - t0
    t0 = time.perf_counter()
    h = jax.device_get(d)
    down = time.perf_counter() - t0
    assert h[0] == x[0]
    return {"h2d_MBps": round(n_mb / up, 1),
            "d2h_MBps": round(n_mb / down, 1)}


def measure_device_resident(mf, batch_size: int, n_batches: int) -> dict:
    """A ModelFunction's compute-side throughput with input already in
    HBM: no host transfer inside the timed region. ``n_batches`` sets
    the timed window — it must be large enough to amortize per-call
    dispatch latency."""
    import jax

    fn = mf.jitted()
    params = mf.device_params()
    (in_name, (shape, dtype)), = mf.input_signature.items()
    out_name = mf.output_names[0]
    rng = np.random.default_rng(1)
    x = rng.integers(0, 255, size=(batch_size,) + tuple(shape)) \
        .astype(dtype)
    dx = {in_name: jax.device_put(x)}
    sync_readback(fn(params, dx)[out_name])  # compile + warm

    t0 = time.perf_counter()
    out = None
    for _ in range(n_batches):
        out = fn(params, dx)
    sync_readback(out[out_name])
    dt = time.perf_counter() - t0
    ips = batch_size * n_batches / dt
    return {"ips": round(ips, 1),
            "batch_ms": round(dt / n_batches * 1000, 2)}


def measure_host_copy(mf, batch_size: int, n_batches: int = 4) -> dict:
    """Host-side staging-copy micro-shape: the SAME program run through
    the production BatchRunner twice — batch-ALIGNED (N a multiple of
    the device batch: the zero-copy hot path, both byte counters must
    read 0) and TAIL-padded (N = aligned + half a batch: only the tail
    stages, through the persistent pad buffer). Reports RunnerMetrics'
    bytes-staged/bytes-copied/transfer-wait counters plus throughput
    for each, so the bench PROVES the ship-path copies went away
    rather than asserting it (the round-1 transfer-strategy lesson
    applied to host copies)."""
    from sparkdl_tpu.runtime.runner import BatchRunner, RunnerMetrics

    (in_name, (shape, dtype)), = mf.input_signature.items()
    rng = np.random.default_rng(3)

    def one(n_rows: int) -> dict:
        size = (n_rows,) + tuple(shape)
        if np.issubdtype(np.dtype(dtype), np.integer):
            # dtype at draw time: the default int64 draw would allocate
            # an 8x transient for a large image corpus before .astype
            x = rng.integers(0, 255, size=size, dtype=dtype)
        else:
            x = rng.integers(0, 255, size=size).astype(dtype)
        metrics = RunnerMetrics()
        runner = BatchRunner(mf, batch_size=batch_size, metrics=metrics)
        runner.run({in_name: x[:batch_size]})  # compile + warm
        # every counter deltas off the warm run: the warmup's
        # device_get stalls on jit compile + first transfer and would
        # otherwise dominate transfer_wait_s
        warm_staged = metrics.bytes_staged
        warm_copied = metrics.bytes_copied
        warm_wait = metrics.transfer_wait_seconds
        t0 = time.perf_counter()
        runner.run({in_name: x})
        dt = time.perf_counter() - t0
        return {"ips": round(n_rows / dt, 1),
                "bytes_staged": int(metrics.bytes_staged - warm_staged),
                "bytes_copied": int(metrics.bytes_copied - warm_copied),
                "transfer_wait_s": round(
                    metrics.transfer_wait_seconds - warm_wait, 4)}

    return {"aligned": one(batch_size * n_batches),
            "tail": one(batch_size * n_batches + batch_size // 2)}
