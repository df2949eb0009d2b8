"""One run of one cell of BENCHMARK.json:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result. Everything that belongs to one
configuration, traffic mix or per-layer metric is a file found by its name:
``configs/<config>.json`` (naming its reference under ``reference/`` and, in its
``correct`` block, its comparer under ``comparers/``), ``traffic/<traffic>.json``
(naming its driver under ``drivers/``) and ``metrics/<metric>.json`` (naming its
reader under ``readers/``; ``metrics/a.b.json`` also serves ``a.b.<suffix>``).

``--rehearsal 1`` is for the builder: it runs on whatever JAX finds, at the traffic
file's tiny ``rehearsal`` sizes, and prints every number under ``cpu_rehearsal.``
so that none can be taken for a device metric."""

from __future__ import annotations

import time

_STARTED = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(path: str) -> dict:
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _applies(metric: dict, cell: dict, reported: set) -> bool:
    """A per-layer metric is read in the cells it lists; one that lists none is read
    wherever the end-to-end metric it moves is reported (the contract's rule for a
    metric that a later PR adds without ``workloads``)."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric["moves"] in reported


def _metric_spec(name: str) -> dict:
    """``metrics/<name>.json``, or the file of the longest dotted prefix of the name:
    ``model.step_ms.json`` serves ``model.step_ms.batch`` and ``model.step_ms.mesh``."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = f"benchmarks/metrics/{'.'.join(parts[:n])}.json"
        if os.path.exists(os.path.join(ROOT, path)):
            return _load(path)
    raise FileNotFoundError(f"no file under benchmarks/metrics/ for the metric {name!r}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = _load("BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(config_entry["file"])
    traffic = _load(f"benchmarks/traffic/{cell['traffic']}.json")

    # the program's own helper places the persistent compile cache: where
    # JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache, a fixed path
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    import jax
    from benchmarks import devices, harness, model

    rehearsal = bool(args.rehearsal)
    devs = jax.devices()[:cell["chips"]] if rehearsal else devices.require_chips(cell["chips"])
    run = harness.Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace), rehearsal=rehearsal,
                      started=_STARTED, devices=devs)
    run.mark("imports and devices")
    if args.trace:
        run.tracer = harness.Tracer()
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    outcome = driver.run(run)

    # per-layer readers see the counters, the trace and the sizes; nothing of the reference
    summary = None
    if run.tracer is not None:
        from benchmarks import tracing
        try:
            summary = tracing.reduce_trace(tracing.find_trace_file(run.tracer.log_dir),
                                           run.tracer.window, run.tracer.spans)
        except ValueError:
            if not rehearsal:  # a CPU rehearsal's trace has no device plane
                raise
    outcome.release()

    # the comparison is the configuration's own: its ``correct`` block names the
    # comparer, which gets the run and what the driver handed back
    comparer = importlib.import_module(f"benchmarks.comparers.{config['correct']['comparer']}")
    is_correct, compared = comparer.compare(run, outcome)
    is_correct = is_correct and outcome.lost == 0

    end_to_end = dict(outcome.end_to_end, setup_s=outcome.setup_s)
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if m["name"] in end_to_end:
                metrics[m["name"]] = end_to_end[m["name"]]
    else:
        peaks = None if rehearsal else devices.peaks_for(devs[0].device_kind)
        view = {"observed": outcome.observed, "trace": summary, "config": config,
                "traffic": traffic, "peaks": peaks, "chips": cell["chips"],
                "flops_per_row": model.flops_per_row(config)}
        for m in bench["per_layer"]:
            if not _applies(m, cell, set(end_to_end)):
                continue
            spec = _metric_spec(m["name"])
            reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
            value = reader.read(view, spec.get("params", {}))
            if value is not None:
                metrics[m["name"]] = float(value)

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
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
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
