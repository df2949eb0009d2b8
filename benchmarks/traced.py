"""A ``--trace 1`` run of one cell with the program's own spans on the trace's clock:

    python3 benchmarks/traced.py --workload <name> --seed <n> --seconds <s> [--keep spans.json]

This is ``run.py --trace 1`` and what ISSUE 26 asked of it: the tracer arms the
program's span tracer and compile log before the driver builds the program, and once
the driver has returned the spans, on the clock of the ``.xplane.pb``, and the scope
map of the program that took most device time go to the readers under
``view["program"]``. The result line is ``run.py``'s, with the metrics of
``traced_per_layer.json`` beside BENCHMARK.json's and ``boundary_parts`` and
``device_blocks`` under ``breakdown``.

It is a file of its own, and not an edit to ``run.py`` and ``harness.Tracer``,
because only a ``benchmark`` PR may edit a file the benchmark has. Such a PR moves
``ProgramTracer.__init__`` into ``harness.Tracer.__init__`` and ``program_view`` with
the two ``breakdown`` keys into ``run.py``, appends ``traced_per_layer.json``'s
entries to BENCHMARK.json's ``per_layer``, and deletes this file and that one. Until
then the driver's runs, which call ``run.py``, report none of these metrics."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import boundary, harness, run as bench_run  # noqa: E402


class ProgramTracer(harness.Tracer):
    """``harness.Tracer`` with the program's tracer and compile log armed from its
    making, before the driver builds the program, so that the compile of set-up is
    an event with its scope map and the spans cover set-up and the whole window."""

    def __init__(self):
        super().__init__()
        from sparkdl_tpu.obs import compile_log, tracer
        self._program = (tracer(), compile_log())
        for part in self._program:
            part.clear()
            part.arm()

    def collect(self) -> dict:
        """The program's spans on the trace's clock and its compiled programs' scope
        maps; both recorders go back to following the environment. Spans lost to the ring are an error."""
        trc, log = self._program
        records, dropped = trc.spans(), trc.dropped
        events = log.events()
        for part in self._program:
            part.arm_from_env()
        if dropped:
            raise RuntimeError(f"the span ring dropped {dropped} spans of this run")
        return {"spans": boundary.spans_on_trace_clock(records, self.zero),
                "window": self.window,
                "programs": {e.module: e.scopes for e in events if e.scopes}}


def program_view(collected: dict, summary, trace_file) -> dict:
    """What the readers find under ``view["program"]``: the spans, the window, and,
    where there is a device plane, the boundary gaps of the idlest device and the
    device seconds by instruction of the dominant program with its scope map."""
    view = dict(collected, boundaries=None, device=None)
    if summary is None:
        return view
    idlest = min(summary.devices.values(), key=lambda d: d.busy_s)
    view["boundaries"] = boundary.boundary_gaps(
        idlest.gaps, collected["spans"], collected["window"])
    program, seconds, total = boundary.instruction_seconds(trace_file, collected["window"])
    view["device"] = {"program": program, "seconds_by_instruction": seconds, "seconds": total,
                      "scopes": collected["programs"].get(program)}
    return view


def pass_intervals(spans) -> list:
    """(start_s, end_s) of each pass of the run's window, which begins where the
    profiler does: from a ``transform.plan`` to the end of the last span that begins
    before the next one, so that the profiler's stop, between two passes, is in none."""
    starts = [s["start"] for s in spans if s["name"] == "transform.plan" and s["start"] >= 0.0]
    passes = []
    for a, b in zip(starts, starts[1:] + [float("inf")]):
        passes.append((a, max(s["end"] for s in spans if a <= s["start"] < b)))
    return passes


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep", help="write the spans and the boundary gaps to this JSON file")
    args = parser.parse_args(argv)

    bench = bench_run._load("BENCHMARK.json")
    per_layer = bench["per_layer"] + bench_run._load("benchmarks/traced_per_layer.json")
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = bench_run._load(config_entry["file"])
    traffic = bench_run._load(f"benchmarks/traffic/{cell['traffic']}.json")

    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    from benchmarks import devices, model, tracing

    rehearsal = bool(args.rehearsal)
    devs = jax.devices()[:cell["chips"]] if rehearsal else devices.require_chips(cell["chips"])
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
                      seconds=args.seconds, trace=True, rehearsal=rehearsal,
                      started=bench_run._STARTED, devices=devs)
    run.mark("imports and devices")
    run.tracer = ProgramTracer()
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    outcome = driver.run(run)
    collected = run.tracer.collect()

    summary = trace_file = None
    try:
        trace_file = tracing.find_trace_file(run.tracer.log_dir)
        summary = tracing.reduce_trace(trace_file, run.tracer.window, run.tracer.spans)
    except ValueError:
        if not rehearsal:  # a CPU rehearsal's trace has no device plane
            raise
    program = program_view(collected, summary, trace_file)
    run.log(f"program spans: {len(program['spans'])} kept, none dropped")
    if args.keep:
        with open(args.keep, "w") as f:
            json.dump({k: program[k] for k in ("window", "spans", "boundaries")}, f)
    for line in boundary.slowest_pass_spans(program["spans"], pass_intervals(program["spans"])):
        run.log(line)
    outcome.release()

    comparer = importlib.import_module(f"benchmarks.comparers.{config['correct']['comparer']}")
    is_correct, compared = comparer.compare(run, outcome)
    is_correct = is_correct and outcome.lost == 0

    end_to_end = dict(outcome.end_to_end, setup_s=outcome.setup_s)
    peaks = None if rehearsal else devices.peaks_for(devs[0].device_kind)
    view = {"observed": outcome.observed, "trace": summary, "config": config,
            "traffic": traffic, "peaks": peaks, "chips": cell["chips"],
            "flops_per_row": model.flops_per_row(config), "program": program}
    metrics, units = {}, {}
    for m in per_layer:
        if not bench_run._applies(m, cell, set(end_to_end)):
            continue
        spec = bench_run._metric_spec(m["name"])
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        value = reader.read(view, spec.get("params", {}))
        if value is not None:
            metrics[m["name"]], units[m["name"]] = float(value), m["unit"]

    prefix = "cpu_rehearsal." if rehearsal else ""
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {
        "correct": bool(is_correct),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": {prefix + k: {"value": v, "unit": units[k]} for k, v in metrics.items()},
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        seen = program["device"]
        result["breakdown"] = {
            "device_ops": summary.top_ops(10), "idle_gaps": summary.top_gaps(10),
            "boundary_parts": boundary.boundary_parts(program["boundaries"]),
            "device_blocks": boundary.device_blocks(
                seen["seconds_by_instruction"], seen["scopes"] or {})[:10]}
    if rehearsal:
        result["rehearsal"] = True
    compared["answers_lost"] = {"value": int(outcome.lost), "limit": 0}
    result["compared"] = compared
    for name, c in compared.items():
        harness.eprint(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    harness.eprint(f"correct: {is_correct} (failed {outcome.failed} of {outcome.attempted})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
