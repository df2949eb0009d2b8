"""Driver ``token_stream``: a batch scoring job. A DataFrame of int32 token rows goes
through ``TensorTransformer.transform`` to a column of per-token log-probabilities read
back on the host, pass after pass without a pause: ``drivers/stream.py`` for a language
model. The window, the warm pass, the rows sampled within a pass and the traced first
seconds are as there; what differs is what a row is (tokens from a Zipf law, not pixels),
where the weights and the program come from (``lm_weights``, ``program_lm``), that the
samples of only the first and the last timed pass are kept (the reference costs seconds a
row), and what is observed beside the rate: what the program's other outputs say, and for
a traced run the compiled program's instruction-to-scope map, by which the kernel readers
find their instructions in the device trace.

Nothing here names a model. The configuration's ``program`` block names the program, the
keywords of its ``model_function`` and its outputs besides ``logprobs``, each with the
function that records it (``program_lm.py``): each is summed over the window's rows,
recorded once the window has closed, and kept for the compared rows. The traffic file says which rows of
a partition are compared: ``sampled_rows`` ``"first_and_last"``, or, where it names none,
``_sample_rows``'s edges and ``sampled_rows_per_partition`` drawn ones."""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import harness, lm_weights, program_lm
from benchmarks.drivers.stream import _partitions, _sample_rows


def run(run: harness.Run) -> harness.Outcome:
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.obs import compile_log
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    traffic = dict(run.traffic)
    if run.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
        # a rehearsal on the CPU cannot hold the configuration: the run's own dict is
        # cut to the traffic file's tiny widths, for the comparer and the readers too
        run.config.update(traffic["config"])
    config = run.config
    block = config["program"]
    others = block.get("outputs", {})  # output name -> its recorder
    batch = int(traffic["device_batch"])
    part_rows = int(traffic["partition_rows"])
    n_parts = int(traffic["partitions_per_pass"])
    stride = int(traffic["partition_stride_rows"])
    tokens = int(traffic["row_tokens"])

    log = compile_log()
    armed_here = run.trace and not log.armed
    if armed_here:  # the compile of set-up then keeps the program's scope map
        log.arm()
    weights = lm_weights.make_weights(config, run.seed)
    run.mark("weights")
    mf = program_lm.model_function(config, weights, tokens, **block.get("options", {}))
    weights = None
    run.mark("program")
    buffer = lm_weights.token_rows(run.seed, part_rows + stride * (n_parts - 1), tokens,
                                   config["vocab_size"], float(traffic["zipf_exponent"]))
    parts = _partitions(buffer, part_rows, n_parts, stride, "tokens")
    run.mark("rows")
    names = ["logprobs", *others]
    transformer = TensorTransformer(
        modelFunction=mf, inputMapping={"tokens": "tokens"},
        outputMapping={name: name for name in names},
        batchSize=batch, useMesh=bool(traffic["use_mesh"]))
    pass_rows = part_rows * n_parts
    kept: dict = {}  # pass index -> (rows of the buffer, {output: its rows})
    totals = dict.fromkeys(others, 0.0)  # over the window's rows

    def column(out, name):
        col = out.column(out.schema.get_field_index(name))
        return col.flatten().to_numpy(zero_copy_only=True).reshape(len(col), -1)

    def picked_rows(index: int) -> list:
        if traffic.get("sampled_rows") == "first_and_last":
            return [sorted({0, part_rows - 1})] * n_parts
        return _sample_rows(run.seed, index, n_parts, part_rows, batch,
                            int(traffic["sampled_rows_per_partition"]))

    def one_pass(index: int, keep: bool) -> int:
        picked = picked_rows(index) if keep else None
        rows, kept_in, kept_out = 0, [], {name: [] for name in names}
        with run.span("bench.pass"):
            stream = transformer.transform(DataFrame.from_batches(parts)).stream()
            for p in range(n_parts):
                with run.span("bench.partition"):
                    out = next(stream)
                got = {name: column(out, name) for name in names}
                back = len(got["logprobs"])
                if back != part_rows:
                    raise RuntimeError(f"partition {p}: {back} rows back, {part_rows} sent")
                rows += part_rows
                if keep:
                    for name, values in got.items():
                        if name in totals:
                            totals[name] = totals[name] + values.sum(axis=0, dtype=np.float64)
                        kept_out[name].append(values[picked[p]].copy())
                    kept_in.extend(p * stride + r for r in picked[p])
            if next(stream, None) is not None:
                raise RuntimeError("the transform returned more partitions than it was given")
        if keep:
            if len(kept) > 1:  # the first timed pass stays, the newest replaces the one before
                del kept[max(kept)]
            kept[index] = (kept_in, {name: np.concatenate(v) for name, v in kept_out.items()})
        return rows

    one_pass(-1, keep=False)  # the first pass pays the compile, allocator growth, the plan
    run.mark("warm pass")
    scopes = None
    if run.trace:
        maps = [e.scopes for e in log.events() if e.scopes]
        scopes = max(maps, key=len) if maps else None
        if armed_here:
            log.arm_from_env()
    metrics = transformer.metrics
    setup_s = time.perf_counter() - run.started

    def counters():
        return np.array([metrics.rows, metrics.seconds, metrics.transfer_wait_seconds])

    passes = []  # (seconds, runner rows, runner seconds, runner transfer wait, traced)

    def timed_pass(traced: bool) -> None:
        before, t = counters(), time.perf_counter()
        one_pass(len(passes), keep=True)
        passes.append((time.perf_counter() - t, *(counters() - before), traced))

    # as stream.py: the profiler's start and stop fall between passes, inside the window
    # but outside every pass; the per-layer shares are taken over the untraced passes
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

    # what the program's other outputs said of the window's rows, into its registry
    recorded = {}
    for name, path in others.items():
        recorded.update(program_lm.recorder(path)(totals[name], rows, tokens, config))

    run.log_setup()
    for i, (s, _, runner_s, wait_s, traced) in enumerate(passes):
        run.log(f"pass {i}: {s:.4f} s, {pass_rows / s:.2f} rows/s, in runner.run {runner_s:.4f} s "
                f"(outside {100 * (1 - runner_s / s):.2f}%), of it waiting for the device "
                f"{wait_s:.4f} s" + (" (traced)" if traced else ""))
    run.log(f"window: {len(passes)} passes, {rows} rows, {window_s:.4f} s, of it in passes "
            f"{sum(p[0] for p in passes):.4f} s"
            + "".join(f"; {k} {v!r}" for k, v in recorded.items()))
    untraced = np.array([p[:4] for p in passes if not p[4]]).sum(axis=0)
    observed = {
        "rows_per_device_step": batch,
        "tokens_per_row": tokens,
        "untraced.pass_seconds": float(untraced[0]),
        "untraced.runner_seconds": float(untraced[2]),
        "untraced.runner_transfer_wait_seconds": float(untraced[3]),
        "device.memory_peak_bytes": peak,
        **recorded,
        "program.scopes": scopes,
    }
    failed = int(abs(rows - sum(p[1] for p in passes)))
    evidence = {"inputs": buffer[np.concatenate([np.asarray(k[0]) for k in kept.values()])],
                **{name: np.concatenate([k[1][name] for k in kept.values()]) for name in names}}

    def release():
        nonlocal transformer, mf, parts, buffer
        transformer = mf = parts = buffer = None
        gc.collect()

    return harness.Outcome(
        attempted=rows, failed=failed, lost=failed, setup_s=setup_s,
        end_to_end={traffic["rate_metric"]: rows / window_s},
        observed=observed, evidence=evidence,
        memory_peak_bytes=peak, release=release)
