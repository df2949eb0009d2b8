"""Measure host<->device transfer strategies on the attached accelerator.

BatchRunner's async-dispatch design must be measured, not asserted.
This tool measures, ending every timed region in a tiny dependent
readback (utils/measure.py):

  link        host->device bandwidth (device_put + 1-element readback)
  readback    device->host bandwidth (device_get of a resident buffer)
  compute     device-resident InceptionV3 featurize img/s (no host IO)
  strategies  end-to-end host-fed img/s for each runner strategy:
                immediate  — enqueue chunk, device_get it right away
                deferred   — enqueue all (bounded), drain at the end
                prefetch   — explicit device_put of chunk i+1 during i
                host_async — copy_to_host_async, gather at the end
  runner_strategy_ips
              the SAME four strategies measured through the production
              BatchRunner (slab outputs + reusable pad staging + the
              built-in depth-N "prefetch" strategy) — what the library
              actually ships, vs the hand-rolled loops above
  host_copy   RunnerMetrics' bytes-staged/bytes-copied/transfer-wait
              counters for batch-aligned vs tail-padded runs (the
              aligned shape must report 0/0: zero-copy ship)

``--sweep`` instead measures a (strategy × depth) grid through the
production BatchRunner — depth is ``max_inflight`` for the queued
strategies and ``prefetch_depth`` for prefetch — and emits per-config
rows/s: the measured priors behind the autotune controller's bounds
(sparkdl_tpu/autotune, docs/PERFORMANCE.md) on whatever host runs it.
``--model/--batch/--rows`` size the sweep (TestNet makes it cheap on
CPU). ``--sweep --workers 0,2,4`` adds the parallel host pipeline's
axis (data/pipeline.py): the fused decode→pack pipeline measured
through a pooled ``LocalEngine`` at each worker count (0 = serial) —
the measured priors behind ``PipelineTarget``'s worker/read-ahead
bounds on this host. ``--sweep --ring 0,2,4`` adds the device-resident
infeed ring's axis (runtime/runner.py InfeedRing): a repeated-corpus
steady pass at each ring depth (0 = no ring) with the steady pass's
ring hits and re-shipped bytes alongside rows/s — the measured priors
behind ``RunnerTarget``'s ``infeed_ring`` bound. ``--sweep
--interleave 0,2,4`` adds the per-device transfer stream axis:
aggregate host->device placement MB/s over this host's local devices,
serial FIFO vs ``interleaved_device_put`` at each width.

Prints one JSON object; run on the real chip (no JAX_PLATFORMS
override) or CPU. Results feed BatchRunner's strategy choice,
the autotuner's priors, and bench.py's reporting.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# the forced-sync methodology lives in ONE place, shared with bench.py
from sparkdl_tpu.utils.measure import (  # noqa: E402
    measure_device_resident,
    measure_host_copy,
    measure_link,
    sync_readback as _sync,
)


def measure_compute(batch_size: int, n_batches: int = 16) -> dict:
    """Device-resident InceptionV3 featurize: img/s and TFLOP/s with no
    host transfer in the timed region."""
    from sparkdl_tpu.models.zoo import getModelFunction

    mf = getModelFunction("InceptionV3", featurize=True)
    out = measure_device_resident(mf, batch_size, n_batches)
    return {"device_ips": out["ips"],
            "device_tflops": round(out["ips"] * 11.5e9 / 1e12, 2),
            "batch_ms": out["batch_ms"]}


def _strategies(batch_size: int, n_rows: int) -> dict:
    import collections

    import jax

    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.runtime.runner import iter_padded_chunks

    mf = getModelFunction("InceptionV3", featurize=True)
    fn = mf.jitted()
    params = mf.device_params()
    images = np.random.default_rng(2).integers(
        0, 255, size=(n_rows, 299, 299, 3), dtype=np.uint8)
    inputs = {"image": images}

    warm = {"image": jax.device_put(images[:batch_size])}
    _sync(fn(params, warm)["features"])

    def immediate():
        outs = []
        for valid, chunk in iter_padded_chunks(inputs, n_rows, batch_size):
            res = fn(params, chunk)
            outs.append(jax.device_get(res["features"])[:valid])
        return np.concatenate(outs)

    def deferred(limit=8):
        pending = collections.deque()
        outs = []
        for valid, chunk in iter_padded_chunks(inputs, n_rows, batch_size):
            pending.append((valid, fn(params, chunk)))
            while len(pending) > limit:
                v, r = pending.popleft()
                outs.append(jax.device_get(r["features"])[:v])
        while pending:
            v, r = pending.popleft()
            outs.append(jax.device_get(r["features"])[:v])
        return np.concatenate(outs)

    def prefetch():
        chunks = list(iter_padded_chunks(inputs, n_rows, batch_size))
        outs = []
        nxt = jax.device_put(chunks[0][1])
        for i, (valid, _) in enumerate(chunks):
            cur = nxt
            if i + 1 < len(chunks):
                nxt = jax.device_put(chunks[i + 1][1])
            res = fn(params, cur)
            outs.append(jax.device_get(res["features"])[:valid])
        return np.concatenate(outs)

    def host_async():
        results = []
        for valid, chunk in iter_padded_chunks(inputs, n_rows, batch_size):
            res = fn(params, chunk)["features"]
            try:
                res.copy_to_host_async()
            except Exception:
                pass
            results.append((valid, res))
        return np.concatenate(
            [jax.device_get(r)[:v] for v, r in results])

    out = {}
    for name, strat in [("immediate", immediate), ("deferred", deferred),
                        ("prefetch", prefetch),
                        ("host_async", host_async)]:
        t0 = time.perf_counter()
        feats = strat()
        dt = time.perf_counter() - t0
        assert feats.shape == (n_rows, 2048)
        out[name] = round(n_rows / dt, 1)
    return out


def _runner_strategies(batch_size: int, n_rows: int) -> dict:
    """The four strategies measured through the PRODUCTION BatchRunner
    (what the library ships: slab outputs, reusable pad staging, the
    built-in depth-1 prefetch), not the hand-rolled loops above."""
    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.runtime.runner import BatchRunner

    mf = getModelFunction("InceptionV3", featurize=True)
    images = np.random.default_rng(2).integers(
        0, 255, size=(n_rows, 299, 299, 3), dtype=np.uint8)
    out = {}
    for name in ("immediate", "deferred", "host_async", "prefetch"):
        runner = BatchRunner(mf, batch_size=batch_size, strategy=name)
        runner.run({"image": images[:batch_size]})  # compile + warm
        t0 = time.perf_counter()
        feats = runner.run({"image": images})["features"]
        dt = time.perf_counter() - t0
        assert feats.shape == (n_rows, 2048)
        out[name] = round(n_rows / dt, 1)
    return out


def _sweep(model: str, batch: int, rows: int,
           depths=(1, 2, 4, 8)) -> list:
    """The (strategy × depth) grid through the production BatchRunner:
    per-config rows/s, best of 2 timed passes (pass 1 absorbs any
    residual jit/cache effects beyond the explicit warmup). ``depth``
    maps to the knob each strategy actually has — ``max_inflight`` for
    deferred/host_async, ``prefetch_depth`` (at the default inflight)
    for prefetch; immediate has no queue and measures once."""
    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.runtime.runner import BatchRunner

    mf = getModelFunction(model, featurize=True)
    in_name = mf.input_names[0]
    shape, dtype = mf.input_signature[in_name]
    images = np.random.default_rng(2).integers(
        0, 255, size=(rows,) + tuple(shape)).astype(dtype)
    grid = []
    for strategy in ("immediate", "deferred", "host_async", "prefetch"):
        for depth in ((None,) if strategy == "immediate" else depths):
            kwargs = {}
            if strategy == "prefetch":
                kwargs["prefetch_depth"] = depth
            elif depth is not None:
                kwargs["max_inflight"] = depth
            runner = BatchRunner(mf, batch_size=batch,
                                 strategy=strategy, **kwargs)
            runner.run({in_name: images[:batch]})    # compile + warm
            best = 0.0
            for _ in range(2):
                t0 = time.perf_counter()
                runner.run({in_name: images})
                best = max(best, rows / (time.perf_counter() - t0))
            grid.append({"strategy": strategy,
                         "max_inflight": runner.max_inflight,
                         "prefetch_depth": runner.prefetch_depth,
                         "rows_per_s": round(best, 1)})
    return grid


def _ring_sweep(model: str, batch: int, rows: int, depths) -> list:
    """The infeed ring's depth axis through the production BatchRunner
    (prefetch strategy — the ring rides the placement look-ahead):
    warmup, one fill pass, then best-of-2 REPEATED-corpus steady
    passes. The steady pass's ring hits and re-shipped bytes ride
    along so the prior records not just rows/s but whether the corpus
    actually fit (corpus_chunks > depth thrashes honestly and the
    numbers say so)."""
    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.obs import default_registry
    from sparkdl_tpu.runtime.runner import BatchRunner, warmup_runner

    reg = default_registry()
    mf = getModelFunction(model, featurize=True)
    in_name = mf.input_names[0]
    shape, dtype = mf.input_signature[in_name]
    images = np.random.default_rng(2).integers(
        0, 255, size=(rows,) + tuple(shape)).astype(dtype)
    grid = []
    for depth in depths:
        runner = BatchRunner(mf, batch_size=batch, strategy="prefetch",
                             infeed_ring=depth)
        warmup_runner(runner)
        runner.run({in_name: images})            # fill pass
        h0 = reg.counter("ship.ring_hits").value
        r0 = reg.counter("ship.bytes_reshipped").value
        best = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            runner.run({in_name: images})
            best = max(best, rows / (time.perf_counter() - t0))
        grid.append({
            "ring": int(runner.infeed_ring),
            "corpus_chunks": -(-rows // batch),
            "rows_per_s": round(best, 1),
            "steady_ring_hits": int(
                reg.counter("ship.ring_hits").value - h0),
            "steady_bytes_reshipped": int(
                reg.counter("ship.bytes_reshipped").value - r0)})
    return grid


def _interleave_sweep(widths, target_mb: int = 8) -> list:
    """The per-device transfer stream axis: aggregate host->device
    placement MB/s over this host's local devices at each interleave
    width (0/1 = serial FIFO ``device_put`` per device shard), best of
    3 passes. On a single-device host every width measures the serial
    path — the degrade the production dispatch takes too."""
    import jax

    from sparkdl_tpu.parallel.mesh import data_sharding, make_mesh
    from sparkdl_tpu.runtime.runner import interleaved_device_put

    devs = jax.local_devices()
    mesh = make_mesh(devices=devs)
    dat = data_sharding(mesh)
    n = len(devs)
    row_bytes = 1024 * 4                          # float32 row
    rows = n * max(1, (target_mb * 1024 * 1024) // (n * row_bytes))
    v = np.random.default_rng(2).random((rows, 1024)).astype(np.float32)
    nbytes = v.nbytes

    def serial() -> None:
        imap = dat.addressable_devices_indices_map(v.shape)
        shards = [jax.device_put(v[idx], d) for d, idx in imap.items()]
        jax.make_array_from_single_device_arrays(
            v.shape, dat, shards).block_until_ready()

    grid = []
    for w in widths:
        w = int(w)
        best, mode = 0.0, "serial"
        for _ in range(3):
            t0 = time.perf_counter()
            if w >= 2 and n >= 2:
                placed = interleaved_device_put({"x": v}, dat, w)
                if placed is None:
                    serial()
                else:
                    placed["x"].block_until_ready()
                    mode = "interleaved"
            else:
                serial()
            best = max(best,
                       nbytes / (time.perf_counter() - t0) / 1e6)
        grid.append({"interleave": w, "devices": n, "mode": mode,
                     "mb_per_s": round(best, 1)})
    return grid


def _workers_sweep(counts, n_images: int = 48,
                   size=(64, 64)) -> list:
    """The parallel host pipeline's worker axis: a fused
    decode→resize→pack pipeline (synthesized textured JPEGs, the bench
    corpus shape) collected through a pooled LocalEngine at each
    worker count — per-config rows/s, best of 2 passes (pass 1 warms
    the page cache / builds the shim). 0 = the serial engine; counts
    above the host's cores still measure (the pool degrades are the
    point of measuring)."""
    import shutil
    import tempfile

    from sparkdl_tpu.data import pipeline as host_pipeline
    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.utils.synth import write_textured_jpegs

    d = tempfile.mkdtemp(prefix="sparkdl_workers_sweep_")
    grid = []
    try:
        write_textured_jpegs(d, n_images)
        for w in counts:
            engine = LocalEngine(pipeline_workers=w)
            try:
                best = 0.0
                for _ in range(2):
                    df = imageIO.readImagesPacked(
                        d, size, numPartitions=8, engine=engine)
                    t0 = time.perf_counter()
                    n = df.collect().num_rows
                    best = max(best,
                               n / (time.perf_counter() - t0))
                effective = host_pipeline.effective_workers(
                    int(w), engine.pipeline_mode, record=False)
                grid.append({
                    "workers": int(w),
                    "effective_workers": effective,
                    "read_ahead": int(engine.pipeline_read_ahead),
                    "mode": (host_pipeline.state().get("mode")
                             or "serial") if effective >= 2
                            else "serial",
                    "rows_per_s": round(best, 1)})
            finally:
                engine.shutdown()
        return grid
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _remote_workers_sweep(counts, n_rows: int = 4096,
                          n_partitions: int = 8) -> list:
    """The disaggregated input service's fleet-size axis
    (sparkdl_tpu/inputsvc/, docs/DATA_SERVICE.md): the SAME decode
    plan over ONE synthetic corpus collected through a remote decode
    fleet at each worker count — in-process ``DecodeServer`` processes
    over the real socket transport, per-config rows/s, best of 2
    passes. 0 = local decode (no fleet); the measured priors behind
    PipelineTarget's ``inputsvc_workers`` knob bound."""
    import pyarrow as pa
    import pyarrow.compute as pc

    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.inputsvc import DecodeServer

    table = pa.table({
        "id": pa.array(range(n_rows), type=pa.int64()),
        "x": pa.array([float(i % 997) for i in range(n_rows)],
                      type=pa.float64()),
    })

    def work(batch):
        i = batch.schema.get_field_index("x")
        col = batch.column("x")
        for _ in range(8):
            col = pc.add(pc.multiply(col, 1.0000001), 0.5)
        return batch.set_column(i, "x", col)

    fleet_max = max([int(c) for c in counts] + [0])
    servers = [DecodeServer().start() for _ in range(fleet_max)]
    endpoints = [f"127.0.0.1:{s.port}" for s in servers]
    grid = []
    try:
        for c in counts:
            c = int(c)
            engine = LocalEngine(
                inputsvc_endpoints=endpoints[:c] if c >= 1 else [])
            try:
                best = 0.0
                for _ in range(2):
                    df = DataFrame.from_table(
                        table, n_partitions, engine).map_batches(
                            work, name="sweep_decode")
                    t0 = time.perf_counter()
                    n = df.collect().num_rows
                    assert n == n_rows, (n, n_rows)
                    best = max(best,
                               n / (time.perf_counter() - t0))
                grid.append({
                    "remote_workers": c,
                    "mode": "remote" if c >= 1 else "local",
                    "rows_per_s": round(best, 1)})
            finally:
                engine.shutdown()
        return grid
    finally:
        for s in servers:
            s.close()


def main() -> None:
    import argparse

    import jax

    from sparkdl_tpu.models.zoo import getModelFunction

    parser = argparse.ArgumentParser(
        prog="python tools/measure_transfer.py",
        description="measure host<->device transfer strategies "
                    "(module docstring)")
    parser.add_argument("--sweep", action="store_true",
                        help="measure the (strategy x depth) grid "
                             "through the production BatchRunner "
                             "instead of the default report")
    parser.add_argument("--model", default="InceptionV3",
                        help="model for --sweep (TestNet is the cheap "
                             "CPU choice)")
    parser.add_argument("--batch", type=int, default=None,
                        help="device batch for --sweep (default: "
                             "platform-sized)")
    parser.add_argument("--rows", type=int, default=None,
                        help="rows per timed pass for --sweep "
                             "(default: 4x batch)")
    parser.add_argument("--workers", default=None,
                        help="comma-separated parallel-host-pipeline "
                             "worker counts to sweep with --sweep "
                             "(0 = serial; e.g. 0,2,4) — the measured "
                             "priors behind the PipelineTarget knob "
                             "bounds (docs/PERFORMANCE.md)")
    parser.add_argument("--ring", default=None,
                        help="comma-separated infeed-ring depths to "
                             "sweep with --sweep (0 = no ring; e.g. "
                             "0,2,4) — the measured priors behind "
                             "RunnerTarget's infeed_ring bound")
    parser.add_argument("--remote-workers", default=None,
                        help="comma-separated remote decode fleet "
                             "sizes to sweep with --sweep (0 = local "
                             "decode; e.g. 0,1,2) — in-process "
                             "DecodeServers over the real socket "
                             "transport; the measured priors behind "
                             "PipelineTarget's inputsvc_workers knob "
                             "(docs/DATA_SERVICE.md)")
    parser.add_argument("--interleave", default=None,
                        help="comma-separated transfer-interleave "
                             "widths to sweep with --sweep (0/1 = "
                             "serial FIFO; e.g. 0,2,4) — aggregate "
                             "device_put MB/s over local devices")
    args = parser.parse_args()

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if args.sweep:
        batch = args.batch or (256 if on_tpu else 8)
        rows = args.rows or batch * 4
        report = {"platform": platform, "model": args.model,
                  "batch": batch, "rows": rows,
                  "sweep": _sweep(args.model, batch, rows)}
        if args.workers is not None:
            counts = [int(tok) for tok in args.workers.split(",")
                      if tok.strip() != ""]
            report["workers_sweep"] = _workers_sweep(counts)
        if args.ring is not None:
            depths = [int(tok) for tok in args.ring.split(",")
                      if tok.strip() != ""]
            report["ring_sweep"] = _ring_sweep(
                args.model, batch, rows, depths)
        if args.interleave is not None:
            widths = [int(tok) for tok in args.interleave.split(",")
                      if tok.strip() != ""]
            report["interleave_sweep"] = _interleave_sweep(widths)
        if args.remote_workers is not None:
            sizes = [int(tok)
                     for tok in args.remote_workers.split(",")
                     if tok.strip() != ""]
            report["remote_workers_sweep"] = _remote_workers_sweep(
                sizes)
        print(json.dumps(report))
        return
    batch = args.batch or (256 if on_tpu else 8)
    rows = args.rows or batch * (4 if on_tpu else 2)
    report = {
        "platform": platform,
        "link": measure_link(32 if on_tpu else 8),
        "compute": measure_compute(batch),
        "strategy_ips": _strategies(batch, rows),
        "runner_strategy_ips": _runner_strategies(batch, rows),
        "host_copy": measure_host_copy(
            getModelFunction("InceptionV3", featurize=True), batch,
            n_batches=4 if on_tpu else 2),
    }
    print(json.dumps(report))


if __name__ == "__main__":
    main()
