"""Driver ``token_stream_routed``: ``drivers/token_stream.py`` for a language model in
which not every layer routes, and whose program is named by the configuration alone. It
is that driver (the same batch scoring job through ``TensorTransformer.transform``, the
same traffic, window, warm pass, sampled passes, traced first seconds and observations),
and differs in three places: the layers that route are taken from the configuration
(``num_hidden_layers - first_k_dense_replace``; all of them where the key is absent),
and both the ``routing`` output and the count of assignments go by them; the program is
built from ``config["program"]["module"]`` over ``program_lm.to_program_tree``, for a
configuration that names its ``router_width`` and so needs none of
``program_lm.model_function``'s ``num_experts``; and ``record_routing`` comes from its
home, ``ops/moe.py``. A ``benchmark`` PR can fold the two (``PERF.md`` section 7)."""

from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmarks import harness, lm_weights, program_lm
from benchmarks.drivers.stream import _partitions, _sample_rows
from benchmarks.program import _same_structure

# the reference's scopes that program_lm's own table does not know, as the program's keys
_BLOCKS = {"LatentAttention_0": "mixer", "DenseMlp_0": "mlp"}


def model_function(config: dict, weights: dict, seq_len: int, routing_stats: bool = False):
    """The program ``sparkdl_tpu.models.<config["program"]["module"]>`` carrying the
    benchmark's weights."""
    module = importlib.import_module(f"sparkdl_tpu.models.{config['program']['module']}")
    tree = program_lm.to_program_tree(
        {"/".join(_BLOCKS.get(scope, scope) for scope in path.split("/")): value
         for path, value in weights.items()})
    _same_structure(tree, module.param_shapes(config))
    return module.model_function(config, tree, seq_len=seq_len, routing_stats=routing_stats)


def run(run: harness.Run) -> harness.Outcome:
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.obs import compile_log
    from sparkdl_tpu.obs.registry import default_registry
    from sparkdl_tpu.ops.moe import record_routing
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer

    traffic = dict(run.traffic)
    if run.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))
        # a rehearsal on the CPU cannot hold the configuration: the run's own dict is
        # cut to the traffic file's tiny widths, for the comparer and the readers too
        run.config.update(traffic["config"])
    config = run.config
    batch = int(traffic["device_batch"])
    part_rows = int(traffic["partition_rows"])
    n_parts = int(traffic["partitions_per_pass"])
    stride = int(traffic["partition_stride_rows"])
    tokens = int(traffic["row_tokens"])
    layers = config["num_hidden_layers"] - config.get("first_k_dense_replace", 0)  # that route
    held = config["experts_held"][1] - config["experts_held"][0]

    log = compile_log()
    armed_here = run.trace and not log.armed
    if armed_here:  # the compile of set-up then keeps the program's scope map
        log.arm()
    weights = lm_weights.make_weights(config, run.seed)
    run.mark("weights")
    mf = model_function(config, weights, tokens, routing_stats=True)
    weights = None
    run.mark("program")
    buffer = lm_weights.token_rows(run.seed, part_rows + stride * (n_parts - 1), tokens,
                                   config["vocab_size"], float(traffic["zipf_exponent"]))
    parts = _partitions(buffer, part_rows, n_parts, stride, "tokens")
    run.mark("rows")
    transformer = TensorTransformer(
        modelFunction=mf, inputMapping={"tokens": "tokens"},
        outputMapping={"logprobs": "logprobs", "routing": "routing"},
        batchSize=batch, useMesh=bool(traffic["use_mesh"]))
    pass_rows = part_rows * n_parts
    kept: dict = {}  # pass index -> (rows of the buffer, their log-probabilities, their routing)
    routing_sum = np.zeros((layers, 1 + held), np.int64)

    def column(out, name):
        col = out.column(out.schema.get_field_index(name))
        return col.flatten().to_numpy(zero_copy_only=True).reshape(len(col), -1)

    def one_pass(index: int, keep: bool) -> int:
        picked = _sample_rows(run.seed, index, n_parts, part_rows, batch,
                              int(traffic["sampled_rows_per_partition"])) if keep else None
        rows, kept_in, kept_out, kept_routing = 0, [], [], []
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
                    routing = column(out, "routing").reshape(part_rows, layers, 1 + held)
                    routing_sum[...] += routing.sum(axis=0)
                    kept_routing.append(routing[picked[p], :, 1:].copy())
                    kept_out.append(scores[picked[p]].copy())
                    kept_in.extend(p * stride + r for r in picked[p])
            if next(stream, None) is not None:
                raise RuntimeError("the transform returned more partitions than it was given")
        if keep:
            if len(kept) > 1:  # the first timed pass stays, the newest replaces the one before
                del kept[max(kept)]
            kept[index] = (kept_in, np.concatenate(kept_out), np.concatenate(kept_routing))
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

    # the routing the device counted for the window's rows, into the registry's counters
    registry = default_registry()
    before = registry.snapshot()
    record_routing(routing_sum, assignments=rows * tokens * config["num_experts_per_tok"] * layers)
    after = registry.snapshot()
    moved = {k: after[k] - before.get(k, 0.0) for k in ("moe.assignments", "moe.assignments_held")}
    load_mean = moved["moe.assignments_held"] / (layers * held)

    run.log_setup()
    for i, (s, _, runner_s, wait_s, traced) in enumerate(passes):
        run.log(f"pass {i}: {s:.4f} s, {pass_rows / s:.2f} rows/s, in runner.run {runner_s:.4f} s "
                f"(outside {100 * (1 - runner_s / s):.2f}%), of it waiting for the device "
                f"{wait_s:.4f} s" + (" (traced)" if traced else ""))
    run.log(f"window: {len(passes)} passes, {rows} rows, {window_s:.4f} s, of it in passes "
            f"{sum(p[0] for p in passes):.4f} s")
    untraced = np.array([p[:4] for p in passes if not p[4]]).sum(axis=0)
    observed = {
        "rows_per_device_step": batch,
        "tokens_per_row": tokens,
        "untraced.pass_seconds": float(untraced[0]),
        "untraced.runner_seconds": float(untraced[2]),
        "untraced.runner_transfer_wait_seconds": float(untraced[3]),
        "device.memory_peak_bytes": peak,
        "moe.assignments": moved["moe.assignments"],
        "moe.assignments_held": moved["moe.assignments_held"],
        "moe.expert_load_max": after["moe.expert_load_max"],
        "moe.expert_load_max_over_mean": after["moe.expert_load_max"] / load_mean if load_mean else None,
        "program.scopes": scopes,
    }
    failed = int(abs(rows - sum(p[1] for p in passes)))
    evidence = {"inputs": buffer[np.concatenate([np.asarray(k[0]) for k in kept.values()])],
                "outputs": np.concatenate([k[1] for k in kept.values()]),
                "routing": np.concatenate([k[2] for k in kept.values()])}

    def release():
        nonlocal transformer, mf, parts, buffer
        transformer = mf = parts = buffer = None
        gc.collect()

    return harness.Outcome(
        attempted=rows, failed=failed, lost=failed, setup_s=setup_s,
        end_to_end={traffic["rate_metric"]: rows / window_s},
        observed=observed, evidence=evidence,
        memory_peak_bytes=peak, release=release)
