"""Driver ``stream``: a batch job. A DataFrame of uint8 image tensors goes through
``TensorTransformer.transform`` to a features column read back on the host, pass
after pass without a pause.

The window's clock starts when its first pass starts and stops at the readback of
the last row of the pass that was running when ``--seconds`` ran out, so a rate is
every row read back over that time: a whole number of passes, partitions and
steps, each with its boundary gap."""

from __future__ import annotations

import gc
import time

import numpy as np
import pyarrow as pa

from benchmarks import harness, model, program


def _partitions(buffer: np.ndarray, rows: int, count: int, stride: int, column: str):
    """``count`` record batches of ``rows`` rows, each a window onto ``buffer`` that
    starts ``stride`` rows after the last: no copy, and no two partitions alike."""
    row_shape = buffer.shape[1:]
    size = int(np.prod(row_shape))
    values = pa.array(buffer.reshape(-1))
    column_all = pa.FixedSizeListArray.from_arrays(values, size)
    field = pa.field(column, column_all.type, metadata={
        b"tensor_shape": ",".join(str(d) for d in row_shape).encode()})
    schema = pa.schema([field])
    return [pa.RecordBatch.from_arrays([column_all.slice(k * stride, rows)], schema=schema)
            for k in range(count)]


def _sample_rows(seed: int, pass_index: int, partitions: int, rows: int, batch: int,
                 per_partition: int) -> list:
    """Rows of one pass whose features are kept for the comparison: for each
    partition its first and last row, the rows either side of its first
    device-batch cut, and others drawn from the seed."""
    rng = np.random.Generator(np.random.PCG64([int(seed), pass_index]))
    picked = []
    for p in range(partitions):
        edges = {0, rows - 1, min(batch, rows) - 1, min(batch, rows - 1)}
        drawn = rng.choice(rows, size=per_partition, replace=False)
        picked.append(sorted(edges | {int(r) for r in drawn}))
    return picked


def run(run: harness.Run) -> harness.Outcome:
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    traffic = dict(run.traffic)
    if run.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
    config = run.config
    batch = int(traffic["device_batch"])
    part_rows = int(traffic["partition_rows"])
    n_parts = int(traffic["partitions_per_pass"])
    stride = int(traffic["partition_stride_rows"])
    use_mesh = bool(traffic["use_mesh"])
    out_name = config["head"]

    weights = model.make_weights(config, run.seed)
    run.mark("weights")
    mf = program.model_function(config, weights)
    run.mark("program")
    buffer = harness.image_rows(run.seed, part_rows + stride * (n_parts - 1),
                                config["input_shape"])
    parts = _partitions(buffer, part_rows, n_parts, stride, "image")
    run.mark("rows")
    transformer = TensorTransformer(
        modelFunction=mf, inputMapping={"image": "image"},
        outputMapping={out_name: out_name}, batchSize=batch, useMesh=use_mesh)
    pass_rows = part_rows * n_parts
    kept_in, kept_out = [], []

    def one_pass(index: int, keep: bool) -> int:
        picked = _sample_rows(run.seed, index, n_parts, part_rows, batch,
                              int(traffic["sampled_rows_per_partition"])) if keep else None
        rows = 0
        with run.span("bench.pass"):
            stream = transformer.transform(DataFrame.from_batches(parts)).stream()
            for p in range(n_parts):
                with run.span("bench.partition"):
                    out = next(stream)
                col = out.column(out.schema.get_field_index(out_name))
                feats = col.flatten().to_numpy(zero_copy_only=True).reshape(len(col), -1)
                if len(feats) != part_rows:
                    raise RuntimeError(f"partition {p}: {len(feats)} rows back, {part_rows} sent")
                rows += len(feats)
                if keep:
                    kept_out.append(feats[picked[p]].copy())
                    kept_in.extend(p * stride + r for r in picked[p])
            if next(stream, None) is not None:
                raise RuntimeError("the transform returned more partitions than it was given")
        return rows

    one_pass(-1, keep=False)  # the first pass pays allocator growth, threads, the plan
    run.mark("warm pass")
    metrics = transformer.metrics
    setup_s = time.perf_counter() - run.started

    def counters():
        return np.array([metrics.rows, metrics.seconds, metrics.transfer_wait_seconds])

    passes = []  # (seconds, runner rows, runner seconds, runner transfer wait, traced)

    def timed_pass(traced: bool) -> None:
        before, t = counters(), time.perf_counter()
        one_pass(len(passes), keep=True)
        passes.append((time.perf_counter() - t, *(counters() - before), traced))

    # The window runs from the first pass's start to the last pass's readback. In a
    # traced run the profiler's start and stop fall between passes, inside the window
    # but outside every pass, so the per-layer shares are taken over the untraced
    # passes that follow, of which there is always one.
    t0 = time.perf_counter()
    if run.trace:
        stop_after = min(float(traffic["trace_seconds"]), run.seconds)
        with run.tracer:
            while time.perf_counter() - t0 < stop_after:
                timed_pass(traced=True)
        timed_pass(traced=False)
    while time.perf_counter() - t0 < run.seconds:
        timed_pass(traced=False)
    window_s = time.perf_counter() - t0
    rows = pass_rows * len(passes)
    peak = harness.memory_peak_bytes(run.devices)

    run.log_setup()
    for i, (s, _, runner_s, wait_s, traced) in enumerate(passes):
        run.log(f"pass {i}: {s:.4f} s, {pass_rows / s:.1f} rows/s, in runner.run {runner_s:.4f} s "
                f"(outside {100 * (1 - runner_s / s):.2f}%), of it waiting for the device "
                f"{wait_s:.4f} s" + (" (traced)" if traced else ""))
    run.log(f"window: {len(passes)} passes, {rows} rows, {window_s:.4f} s, of it in passes "
            f"{sum(p[0] for p in passes):.4f} s")
    untraced = np.array([p[:4] for p in passes if not p[4]]).sum(axis=0)
    observed = {
        "rows_per_device_step": batch,
        "untraced.pass_seconds": float(untraced[0]),
        "untraced.runner_seconds": float(untraced[2]),
        "untraced.runner_transfer_wait_seconds": float(untraced[3]),
        "device.memory_peak_bytes": peak,
    }
    failed = int(abs(rows - sum(p[1] for p in passes)))
    evidence = {"inputs": buffer[np.asarray(kept_in)], "outputs": np.concatenate(kept_out)}

    def release():
        nonlocal transformer, mf, parts, buffer
        transformer = mf = parts = buffer = None
        gc.collect()

    return harness.Outcome(
        attempted=rows, failed=failed, lost=failed, setup_s=setup_s,
        end_to_end={traffic["rate_metric"]: rows / window_s},
        observed=observed, evidence=evidence,
        memory_peak_bytes=peak, release=release)
