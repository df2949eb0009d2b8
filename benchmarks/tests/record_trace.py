"""Records the small device trace that the reducer's tests read.

Run on the chip: ``chiprun -- python3 benchmarks/tests/record_trace.py``.
It drives a small jitted convolution in two "passes" of three steps, with a
host-side pause between them, under the same ``TraceAnnotation`` names the
drivers use, and writes ``chiprun_out/trace_small.xplane.pb``, a
``trace_small.spans.json`` with the window and the spans on the trace's clock (what
``harness.Tracer`` hands the reducer in a run; here, at this tiny size, the host
tracer is on and they are taken from its plane) and a ``trace_small.summary.json``
of what the profiler's planes and lines hold. The first two are kept in ``data/``.
"""

import glob
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    out_dir = os.path.join("chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))

    @jax.jit
    def step(w, x):
        y = jax.lax.conv_general_dilated(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.mean(jax.nn.relu(y).astype(jnp.float32), axis=(1, 2))

    w = jnp.ones((3, 3, 64, 64), jnp.float32) * 0.01
    x = np.ones((64, 56, 56, 64), np.float32)
    np.asarray(step(w, x))
    log_dir = os.path.join(out_dir, "trace_small")
    shutil.rmtree(log_dir, ignore_errors=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for p in range(2):
            with jax.profiler.TraceAnnotation("bench.pass", index=p):
                for s in range(3):
                    with jax.profiler.TraceAnnotation("bench.partition", index=s):
                        np.asarray(step(w, x))
            with jax.profiler.TraceAnnotation("bench.pass_boundary"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    print("traced_s", time.perf_counter() - t0)
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    print(paths, [os.path.getsize(p) for p in paths])
    shutil.copy(paths[0], os.path.join(out_dir, "trace_small.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(paths[0])
    window, spans = None, []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == "bench.window":
                        window = [e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9]
                    elif e.name.startswith("bench."):
                        spans.append([e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9])
    with open(os.path.join(out_dir, "trace_small.spans.json"), "w") as f:
        json.dump({"window": window, "spans": sorted(spans, key=lambda s: s[1])}, f, indent=1)
    summary = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            first = events[0] if events else None
            lines.append({
                "name": line.name, "n_events": len(events),
                "names": dict(sorted(names.items(), key=lambda kv: -kv[1])[:25]),
                "first": None if first is None else {
                    "name": first.name, "start_ns": first.start_ns,
                    "duration_ns": first.duration_ns,
                    "stats": {k: str(v)[:80] for k, v in list(first.stats)[:20]}},
            })
        summary.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(out_dir, "trace_small.summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for pl in summary:
        print("PLANE", pl["plane"])
        for ln in pl["lines"]:
            print("  LINE", ln["name"], ln["n_events"], list(ln["names"])[:8])
    return 0


if __name__ == "__main__":
    sys.exit(main())
