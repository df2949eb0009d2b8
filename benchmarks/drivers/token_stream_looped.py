"""Driver ``token_stream_looped``: ``drivers/token_stream_routed.py`` for a dense language
model whose layers run several times over one set of weights. It is that driver (the same
batch scoring job through ``TensorTransformer.transform``, the same window, warm pass,
sampled passes, traced first seconds and observations), and differs where that one asks
every configuration for a router: there are no ``experts_held``, no ``num_experts_per_tok``
and no ``routing`` output here. The model's second output is ``exit_pdf`` (the exit
distribution, ``total_ut_steps`` numbers a row), which is kept for the compared rows and
summed over the window's rows into the registry by ``models/ouro.py::record_exit``; the
reference's stacked leaves (``layers/q_proj [48, ...]``) are the program's own, so
``program_lm.to_program_tree`` maps them with no table; and the compared rows are each
partition's first and last (``_sample_rows`` would take all four of a partition of four).
A ``benchmark`` PR can fold the three token drivers (``PERF.md`` section 7)."""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmarks import harness, lm_weights, program_lm
from benchmarks.drivers.stream import _partitions
from benchmarks.program import _same_structure


def model_function(config: dict, weights: dict, seq_len: int):
    """The program ``sparkdl_tpu.models.<config["program"]["module"]>`` carrying the
    benchmark's weights."""
    module = importlib.import_module(f"sparkdl_tpu.models.{config['program']['module']}")
    tree = program_lm.to_program_tree(weights)
    _same_structure(tree, module.param_shapes(config))
    return module.model_function(config, tree, seq_len=seq_len)


def run(run: harness.Run) -> harness.Outcome:
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.obs import compile_log
    from sparkdl_tpu.obs.registry import default_registry
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    traffic = dict(run.traffic)
    if run.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
        # a rehearsal on the CPU cannot hold the configuration: the run's own dict is
        # cut to the traffic file's tiny widths, for the comparer and the readers too
        run.config.update(traffic["config"])
    config = run.config
    record_exit = importlib.import_module(
        f"sparkdl_tpu.models.{config['program']['module']}").record_exit
    batch = int(traffic["device_batch"])
    part_rows = int(traffic["partition_rows"])
    n_parts = int(traffic["partitions_per_pass"])
    stride = int(traffic["partition_stride_rows"])
    tokens = int(traffic["row_tokens"])
    passes_of_loop = int(config["total_ut_steps"])

    log = compile_log()
    armed_here = run.trace and not log.armed
    if armed_here:  # the compile of set-up then keeps the program's scope map
        log.arm()
    weights = lm_weights.make_weights(config, run.seed)
    run.mark("weights")
    mf = model_function(config, weights, tokens)
    weights = None
    run.mark("program")
    buffer = lm_weights.token_rows(run.seed, part_rows + stride * (n_parts - 1), tokens,
                                   config["vocab_size"], float(traffic["zipf_exponent"]))
    parts = _partitions(buffer, part_rows, n_parts, stride, "tokens")
    run.mark("rows")
    transformer = TensorTransformer(
        modelFunction=mf, inputMapping={"tokens": "tokens"},
        outputMapping={"logprobs": "logprobs", "exit_pdf": "exit_pdf"},
        batchSize=batch, useMesh=bool(traffic["use_mesh"]))
    pass_rows = part_rows * n_parts
    picked = sorted({0, part_rows - 1})  # of every partition: its first and last row
    kept: dict = {}  # pass index -> (rows of the buffer, their log-probabilities, their exit_pdf)
    exit_sum = np.zeros(passes_of_loop, np.float64)

    def column(out, name):
        col = out.column(out.schema.get_field_index(name))
        return col.flatten().to_numpy(zero_copy_only=True).reshape(len(col), -1)

    def one_pass(index: int, keep: bool) -> int:
        rows, kept_in, kept_out, kept_pdf = 0, [], [], []
        with run.span("bench.pass"):
            stream = transformer.transform(DataFrame.from_batches(parts)).stream()
            for p in range(n_parts):
                with run.span("bench.partition"):
                    out = next(stream)
                scores = column(out, "logprobs")
                if len(scores) != part_rows:
                    raise RuntimeError(f"partition {p}: {len(scores)} rows back, {part_rows} sent")
                rows += len(scores)
                if keep:
                    pdf = column(out, "exit_pdf")
                    exit_sum[...] += pdf.sum(axis=0, dtype=np.float64)
                    kept_pdf.append(pdf[picked].copy())
                    kept_out.append(scores[picked].copy())
                    kept_in.extend(p * stride + r for r in picked)
            if next(stream, None) is not None:
                raise RuntimeError("the transform returned more partitions than it was given")
        if keep:
            if len(kept) > 1:  # the first timed pass stays, the newest replaces the one before
                del kept[max(kept)]
            kept[index] = (kept_in, np.concatenate(kept_out), np.concatenate(kept_pdf))
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

    # what the loop did for the window's rows, into the registry's counters
    registry = default_registry()
    before = registry.snapshot()
    record_exit(exit_sum, rows)
    after = registry.snapshot()

    run.log_setup()
    for i, (s, _, runner_s, wait_s, traced) in enumerate(passes):
        run.log(f"pass {i}: {s:.4f} s, {pass_rows / s:.2f} rows/s, in runner.run {runner_s:.4f} s "
                f"(outside {100 * (1 - runner_s / s):.2f}%), of it waiting for the device "
                f"{wait_s:.4f} s" + (" (traced)" if traced else ""))
    run.log(f"window: {len(passes)} passes, {rows} rows, {window_s:.4f} s, of it in passes "
            f"{sum(p[0] for p in passes):.4f} s; exit_pdf mean {(exit_sum / rows).round(4).tolist()}")
    untraced = np.array([p[:4] for p in passes if not p[4]]).sum(axis=0)
    observed = {
        "rows_per_device_step": batch,
        "tokens_per_row": tokens,
        "untraced.pass_seconds": float(untraced[0]),
        "untraced.runner_seconds": float(untraced[2]),
        "untraced.runner_transfer_wait_seconds": float(untraced[3]),
        "device.memory_peak_bytes": peak,
        "loop.rows": after["loop.rows"] - before.get("loop.rows", 0.0),
        "loop.exit_step_mean": after["loop.exit_step_mean"],
        "program.scopes": scopes,
    }
    failed = int(abs(rows - sum(p[1] for p in passes)))
    evidence = {"inputs": buffer[np.concatenate([np.asarray(k[0]) for k in kept.values()])],
                "outputs": np.concatenate([k[1] for k in kept.values()]),
                "exit_pdf": np.concatenate([k[2] for k in kept.values()])}

    def release():
        nonlocal transformer, mf, parts, buffer
        transformer = mf = parts = buffer = None
        gc.collect()

    return harness.Outcome(
        attempted=rows, failed=failed, lost=failed, setup_s=setup_s,
        end_to_end={traffic["rate_metric"]: rows / window_s},
        observed=observed, evidence=evidence,
        memory_peak_bytes=peak, release=release)
