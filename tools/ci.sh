#!/usr/bin/env bash
# One-command verification gate (SURVEY §4 item 6 — the reference's
# Travis matrix ran `sbt test` + the python suite; this is the TPU
# build's equivalent, green from a fresh clone with no network):
#
#   1. build the native host shim (g++ + libjpeg; falls back to the
#      PIL path when unavailable, which the suite also covers)
#   2. run the full pytest suite on an 8-virtual-device CPU mesh
#      (the local-mode-Spark analogue: every multi-chip code path
#      executes without TPU hardware)
#   3. compile-check + execute the multi-chip training/inference
#      dryrun (__graft_entry__.dryrun_multichip)
#   4. per-request SLO gate (docs/OBSERVABILITY.md): an injected
#      deadline-miss burst must surface as sparkdl_slo_* budget/
#      burn-rate series on /metricsz with availability burn rate > 0 —
#      while the latency percentile population stays successes-only.
#   5. watchdog + flight-recorder + telemetry gate: a synthetic stall
#      (dispatcher blocked inside a dispatch) under a short watchdog
#      threshold must fire the stall verdict, flip /healthz to 503,
#      and produce a flight bundle carrying ≥1 span, the serve queue
#      state, and a watchdog.stalls ≥ 1 registry snapshot; after
#      recovery /metricsz must scrape as valid Prometheus text.
#   6. static analysis: sparkdl-lint (docs/LINT.md — H1 transfers,
#      H2 retrace, H3 locks, H4 quiesce, H5 clock discipline, H6
#      metric cardinality, H12 exception-flow accounting, plus the
#      whole-program passes H7 lock-order cycles / H8
#      blocking-under-lock / H9 docs contract drift / H10 jit-purity
#      closure / H11 resource lifecycle) must report ZERO unsuppressed
#      findings across the package AND tools/ + examples/, plus the
#      ruff baseline when installed
#   7. analyzer machine contract: `--json` output schema, and the
#      per-file result cache's correctness — a cold run misses, a
#      second run hits every file, a touched file (and only it)
#      re-analyzes, with identical findings either way
#   8. effect-system gate (docs/LINT.md): the seeded fixture for each
#      of H10 (jitted fn transitively reaching a registry counter
#      through two modules, witness chain printed) / H11 (unclosed
#      ModelServer) / H12 (swallowing serve handler) must be CAUGHT,
#      the package + tools/ + examples/ must be clean under all
#      thirteen rules, --sarif must emit well-formed SARIF 2.1.0, and
#      --changed-only must smoke (the tools/lint.sh --fast loop)
#   9. fault-drill gate (docs/RESILIENCE.md): with SPARKDL_TPU_FAULTS
#      arming a 10% transient fault rate at the serve dispatch site,
#      a concurrent soak must show faults.injected > 0 and
#      serve.retries > 0 with ZERO lost requests (every future
#      resolves — success or typed failure, none dropped or
#      double-answered), /healthz back at 200 after the drill, and
#      the availability burn rate back under 1.0 once the drill
#      window rolls off — recovery proved, not asserted
#  10. throughput-hazard gate (docs/LINT.md): the seeded fixture for
#      each of H14 (hot-loop `.item()` host sync, witness chain
#      printed), H15 (undonated jit call with a dead device-array
#      argument), and H16 (dtype-less float64 promotion into device
#      arithmetic) must be CAUGHT; the dead-vs-escaping H15 negative
#      must stay silent; SARIF must list all nineteen rules; and the
#      analyzer's --json timing block must show the dataflow closure
#      staying cheap (warm cached run: every file hits, wall time
#      bounded) so the --changed-only fast loop keeps its point
#  11. live-roofline ledger gate (docs/PERFORMANCE.md "Reading the
#      live roofline"): live traffic must surface
#      sparkdl_ledger_util_* (with # HELP) on /metricsz, the ledger
#      section with its history ring on /statusz AND in a flight
#      bundle
#  12. compile-forensics gate (docs/OBSERVABILITY.md "Compile
#      forensics", docs/SERVING.md "diagnosing a compile storm"): a
#      warmed serve soak followed by an injected off-ladder shape
#      must show compile.unexpected_retraces > 0 with the retrace
#      diff NAMING the changed argument, a flight dump carrying the
#      attribution, and the /healthz detail flipped — while the soak
#      before the injection stays at zero; and `report --compile`
#      must read the drill's exported trace
#  13. parallel-host-pipeline gate (docs/PERFORMANCE.md "Parallel
#      host pipeline"): a process-pool overlap drill
#      must show overlap_ratio > 1.1 when >= 2 cores exist; an
#      ordered-re-merge drill under adversarial scheduling must show
#      ZERO lost/duplicated rows by identity; an injected stalled
#      worker must fire a watchdog stall NAMING the pipeline source
#      and recover; a PipelineTarget-armed controller must settle
#      with zero oscillations; and the pipeline state must ride
#      /statusz and a flight bundle
#  14. static-race gate (docs/LINT.md "The static race layer"): the
#      seeded fixture for each of H17 (unguarded access to a
#      majority-guarded attribute, witness naming both thread roots +
#      the lock + the vote), H18 (mutable local handed to a thread
#      and mutated on both sides, both mutation lines named), and H19
#      (check-then-act split across two holds of one lock, both hold
#      lines named) must be CAUGHT with full witness content; the
#      locked/atomic/double-checked negatives must stay silent; SARIF
#      must be well-formed with all nineteen rules; the package +
#      tools/ + examples/ must be clean under all nineteen; and the
#      warm cached run must hit every file with total_s < 60
#  15. cross-process telemetry gate (docs/OBSERVABILITY.md
#      "Cross-process telemetry"): an ARMED (SPARKDL_TPU_TRACE=1)
#      process-pool stream must export ONE merged Perfetto trace with
#      each worker on its own process track (pid >= 1000), worker
#      decode spans time-aligned inside the parent stream's window; a
#      live /metricsz scrape must carry sparkdl_worker_* series with
#      # HELP; an injected pipeline.worker_decode transient fault
#      (shipped to workers through the telemetry config) must be
#      retried by the parent with ZERO lost rows and its worker-side
#      counters mirrored as worker.all.faults.* in the parent
#      registry; a pipeline.worker_death drill (worker process
#      os._exit mid-task) must surface pipeline.worker_deaths, a
#      typed PipelineWorkerError, and a flight bundle whose workers[]
#      names the dead worker; and `report --workers` must read the
#      merged trace (with the bundle join)
#  16. input-service gate (docs/DATA_SERVICE.md): a TWO-PROCESS
#      localhost drill — the client process streams the corpus
#      through one `python -m sparkdl_tpu.inputsvc serve` DecodeServer
#      with ZERO lost/duplicated rows (exact id identity) under a 10%
#      inputsvc.rpc transient injection; the ledger window's
#      decode_workers must scale by the live remote fleet; killing
#      the worker mid-run must fail over to local decode LOUDLY
#      (counted fallback, correct rows); and a second snapshot-backed
#      epoch must stream with pipeline decode busy-seconds ≈ 0 at
#      throughput >= the serial-decode baseline
#  17. fleet gate (docs/SERVING.md "Fleet control plane"): three
#      drills on one registry-managed model. (a) hot-swap under
#      concurrent submit load — every in-flight future resolves
#      (ZERO dropped), every output is old-weights or new-weights
#      (never mixed), post-swap outputs flip to the new weights, and
#      the steady replicas record zero compiles and zero
#      unexpected_retraces across the swap; (b) corrupt-cache
#      fail-closed — a byte-flipped warm-start blob must be COUNTED
#      (fleet.warmstart_corruptions), deleted, and fallen back to a
#      cold compile that still answers correctly; (c) scale-out
#      proof — TWO fresh child processes, identical but for the
#      cache env: the one reading the persisted
#      SPARKDL_TPU_FLEET_CACHE must record ZERO jit compiles (AOT
#      deserialize only) and land its first request far under the
#      cache-less child's (same fixed costs, minus the compile)
#
# Usage: tools/ci.sh [pytest args...]
#   e.g. tools/ci.sh -x -k "not multiproc"   # narrow during dev
# Env:  SPARKDL_TPU_CI_SKIP_SUITE=1  skip step 2 (keep the rest)

set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
export KERAS_BACKEND=jax
export TF_CPP_MIN_LOG_LEVEL=3
export CUDA_VISIBLE_DEVICES=-1
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"

echo "== [1/17] native shim build =="
python - <<'EOF'
from sparkdl_tpu import native
ok = native.available()
print(f"native shim: {'built' if ok else 'UNAVAILABLE (PIL fallback)'}"
      f", libjpeg: {native.has_jpeg() if ok else False}")
EOF

if [ "${SPARKDL_TPU_CI_SKIP_SUITE:-0}" != "1" ]; then
  echo "== [2/17] test suite (8-virtual-device CPU mesh) =="
  python -m pytest tests/ -q "$@"
else
  echo "== [2/17] SKIPPED (SPARKDL_TPU_CI_SKIP_SUITE=1) =="
fi

echo "== [3/17] multi-chip dryrun (8 virtual devices) =="
python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
from __graft_entry__ import dryrun_multichip
dryrun_multichip(8)
print("dryrun_multichip(8): ok")
EOF

echo "== [4/17] per-request SLO gate (docs/OBSERVABILITY.md) =="
# burn-rate gate: an injected deadline-miss burst must read as
# sparkdl_slo_* budget/burn-rate series on /metricsz (burn > 0), while
# the latency reservoir's percentile population stays successes-only
python - <<'EOF'
import json
import re
import time
import urllib.request

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs.slo import slo_tracker
from sparkdl_tpu.serve import DeadlineExceeded, ModelServer, ServeConfig

slo_tracker().clear()


def slow_apply(params, inputs):
    time.sleep(0.05)        # each dispatch holds the lane ~50 ms
    return {"y": np.asarray(inputs["x"], np.float32) * 2.0}


mf = ModelFunction(slow_apply, None,
                   input_signature={"x": ((2,), np.float32)},
                   output_names=["y"], backend="host", name="slogate")
server = ModelServer(ServeConfig(max_wait_s=0.0))
server.register("slogate", mf, batch_size=4)
tel = server.serve_telemetry()

x = np.zeros((2, 2), np.float32)
# the burst: the first dispatch occupies the lane for 50 ms, so these
# 1 ms deadlines expire queued and fail BEFORE dispatch
futs = [server.submit({"x": x}, deadline=0.001) for _ in range(8)]
missed = 0
for f in futs:
    try:
        f.result(timeout=30)
    except DeadlineExceeded:
        missed += 1
assert missed >= 1, "no deadline misses in the injected burst"
# successes after the burst: the latency population
oks = [server.submit({"x": x}) for _ in range(3)]
for f in oks:
    f.result(timeout=30)

with urllib.request.urlopen(tel.url("/metricsz"), timeout=5) as r:
    body = r.read().decode()
for series in ("sparkdl_slo_availability_burn_rate",
               "sparkdl_slo_availability_budget_remaining",
               "sparkdl_slo_latency_burn_rate",
               "sparkdl_slo_latency_budget_remaining"):
    assert re.search(rf"^{series} ", body, re.M), \
        f"{series} missing from /metricsz"
burn = float(re.search(
    r"^sparkdl_slo_availability_burn_rate ([-+0-9.e]+)", body,
    re.M).group(1))
assert burn > 0.0, f"availability burn rate {burn} after misses"

with urllib.request.urlopen(tel.url("/statusz"), timeout=5) as r:
    st = json.load(r)
assert "slo" in st and "availability" in st["slo"]["objectives"], \
    sorted(st)
m = st["servers"][0]["metrics"]
# the separate-population fix (pinned harder in
# tests/test_request_obs.py): misses count in the availability
# stream; the latency percentiles are computed over successes only —
# with every success taking ~50 ms and every miss queued ~1 ms, a
# polluted percentile population would drag p50 far below the
# dispatch floor
assert m["deadline_misses"] == missed, m
assert m["failures"] == 0, m
assert m["latency_p50_ms"] >= 40.0, m
server.close()
tel.close()
print(json.dumps({"slo_gate": "ok", "deadline_misses": missed,
                  "availability_burn_rate": burn}))
EOF

echo "== [5/17] watchdog + flight recorder + telemetry gate (injected stall) =="
SPARKDL_TPU_FLIGHT_DIR=/tmp python - <<'EOF'
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import flight, watchdog
from sparkdl_tpu.serve import ModelServer, ServeConfig

rec = flight.recorder()
rec.arm()                         # span retention + SIGUSR2 + triggers
wd = watchdog.watchdog()
wd.arm(threshold_s=0.3)           # short threshold for the injection

# the synthetic stall: a host-backend model whose apply blocks, so the
# serve dispatcher wedges INSIDE a dispatch (the silent-hang shape the
# collective-launch deadlock had)
gate = threading.Event()


def blocked_apply(params, inputs):
    gate.wait()
    return {"y": np.asarray(inputs["x"], np.float32) * 2.0}


mf = ModelFunction(blocked_apply, None,
                   input_signature={"x": ((2,), np.float32)},
                   output_names=["y"], backend="host", name="wedge")
server = ModelServer(ServeConfig(max_wait_s=0.0, drain_timeout_s=5.0))
server.register("wedge", mf, batch_size=4)
tel = server.serve_telemetry()    # localhost, OS-picked port

fut = server.submit({"x": np.zeros((2, 2), np.float32)})
deadline = time.perf_counter() + 15.0
while wd.healthy():
    assert time.perf_counter() < deadline, \
        "watchdog did not fire within the threshold"
    time.sleep(0.02)


def get(path):
    try:
        with urllib.request.urlopen(tel.url(path), timeout=5) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


code, body = get("/healthz")
assert code == 503, (code, body)          # stalled -> unhealthy
health = json.loads(body)
assert health["status"] == "stalled", health
assert health["stalled_sources"], health

# the stall must have produced a forensics bundle (written on the
# monitor thread AFTER the verdict flips — poll briefly)
deadline = time.perf_counter() + 10.0
while rec.last_dump_path is None:
    assert time.perf_counter() < deadline, \
        "watchdog stall produced no flight bundle"
    time.sleep(0.02)
bundle_path = rec.last_dump_path
with open(bundle_path) as f:
    bundle = json.load(f)
assert bundle["schema"].startswith("sparkdl-flight/"), bundle["schema"]
assert bundle["span_count"] >= 1, bundle["span_count"]
assert bundle["registry"].get("watchdog.stalls", 0) >= 1, \
    {k: v for k, v in bundle["registry"].items() if "watchdog" in k}
[srv] = bundle["serve"]
assert "wedge" in srv["models"], srv
assert srv["models"]["wedge"]["runner"]["type"], srv

gate.set()                        # un-wedge; the dispatcher drains
out = fut.result(timeout=15)
assert out["y"].shape == (2, 2), out["y"].shape

# recovery: the verdict clears on its own once progress resumes
deadline = time.perf_counter() + 10.0
while not wd.healthy():
    assert time.perf_counter() < deadline, "no stall recovery"
    time.sleep(0.02)
code, body = get("/healthz")
assert code == 200, (code, body)

# /metricsz must parse as Prometheus text exposition format — and
# every exported sample must carry BOTH its # HELP and # TYPE line
# (render_prometheus emits the pair; a renderer regression that drops
# either fails here, line-by-line)
code, body = get("/metricsz")
assert code == 200, (code, body)
sample = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|nan|inf)$")
n = 0
help_names, type_names, sample_names = set(), set(), set()
for line in body.strip().splitlines():
    if not line:
        continue
    if line.startswith("#"):
        m = re.match(r"^# (TYPE|HELP) ([a-zA-Z_:][a-zA-Z0-9_:]*) .+$",
                     line)
        assert m, repr(line)
        (help_names if m.group(1) == "HELP" else type_names).add(
            m.group(2))
        continue
    assert sample.match(line), f"bad Prometheus line: {line!r}"
    sample_names.add(line.split("{")[0].split(" ")[0])
    n += 1
assert n > 0, "empty /metricsz"
assert sample_names <= type_names, \
    f"samples missing # TYPE: {sorted(sample_names - type_names)[:8]}"
assert type_names == help_names, \
    (f"HELP/TYPE mismatch: TYPE-only "
     f"{sorted(type_names - help_names)[:8]}, HELP-only "
     f"{sorted(help_names - type_names)[:8]}")
assert "sparkdl_watchdog_stalls" in body, body[:400]
assert "sparkdl_flight_dumps" in body, body[:400]

code, body = get("/statusz")
assert code == 200
st = json.loads(body)
assert st["servers"][0]["models"]["wedge"]["queue_rows"] == 0, st
assert st["flight"]["dumps"] >= 1, st["flight"]

server.close()
tel.close()
wd.disarm()
print(json.dumps({"stall_gate": "ok", "prom_samples": n,
                  "bundle": bundle_path,
                  "stalls_fired": wd.stalls_fired}))
EOF

echo "== [6/17] static analysis (sparkdl-lint + ruff baseline) =="
# no targets: lint.sh's default sweep = sparkdl_tpu + tools + examples
tools/lint.sh

echo "== [7/17] analyzer machine contract (--json schema + cache correctness) =="
rm -f /tmp/sparkdl_lint_ci_cache.json
SPARKDL_TPU_LINT_CACHE=/tmp/sparkdl_lint_ci_cache.json python - <<'EOF'
import json
import os
import subprocess
import sys

env = dict(os.environ)


def run_json(*extra):
    r = subprocess.run(
        [sys.executable, "-m", "sparkdl_tpu.analysis", "--json",
         *extra, "sparkdl_tpu", "tools", "examples"],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                               r.stderr[-2000:])
    return json.loads(r.stdout)


# --json schema: the machine contract CI and editors consume
d1 = run_json()
for key in ("findings", "unsuppressed", "suppressed", "rules",
            "by_rule", "targets", "cache"):
    assert key in d1, f"--json missing {key!r}: {sorted(d1)}"
assert d1["unsuppressed"] == 0, d1["findings"]
assert d1["suppressed"] > 0, "expected the known suppressed findings"
assert set(d1["rules"]) >= {"H1", "H2", "H3", "H4", "H5", "H6",
                            "H7", "H8", "H9", "H10", "H11", "H12",
                            "H13", "H14", "H15", "H16"}, \
    d1["rules"]
for f in d1["findings"]:
    for k in ("rule", "path", "line", "col", "message", "suppressed"):
        assert k in f, (k, f)

# cache correctness: cold run missed everything ...
assert d1["cache"]["enabled"] is True, d1["cache"]
assert d1["cache"]["hits"] == 0 and d1["cache"]["misses"] > 0, \
    d1["cache"]

# ... a second run hits every file with IDENTICAL findings ...
d2 = run_json()
assert d2["cache"]["misses"] == 0, d2["cache"]
assert d2["cache"]["hits"] == d1["cache"]["misses"], \
    (d1["cache"], d2["cache"])
assert d2["unsuppressed"] == d1["unsuppressed"]
assert d2["suppressed"] == d1["suppressed"]

# ... and touching one file re-analyzes that file and only it
victim = os.path.join("sparkdl_tpu", "serve", "batching.py")
os.utime(victim)
d3 = run_json()
assert d3["cache"]["misses"] == 1, d3["cache"]
assert d3["cache"]["hits"] == d2["cache"]["hits"] - 1, \
    (d2["cache"], d3["cache"])
assert d3["suppressed"] == d1["suppressed"]

print(json.dumps({"analyzer_gate": "ok",
                  "files": d1["cache"]["misses"],
                  "suppressed": d1["suppressed"],
                  "by_rule": {k: v for k, v in d1["by_rule"].items()
                              if v["suppressed"]}}))
EOF

echo "== [8/17] effect-system gate (H10/H11/H12 fixtures + SARIF + --changed-only) =="
python - <<'EOF'
import json
import os
import tempfile

from sparkdl_tpu.analysis import analyze_paths

# seeded fixtures: each of the three new rules must CATCH its shape
with tempfile.TemporaryDirectory() as d:
    def w(name, src):
        with open(os.path.join(d, name), "w") as f:
            f.write(src)

    # H10: jitted fn -> helper module -> metrics module counter
    w("metrics_mod.py", "def bump(reg):\n"
                        "    reg.counter('train.steps').add()\n")
    w("helper_mod.py", "from metrics_mod import bump\n"
                       "def helper(x, reg):\n"
                       "    bump(reg)\n"
                       "    return x\n")
    w("train_mod.py", "import jax\n"
                      "from helper_mod import helper\n"
                      "@jax.jit\n"
                      "def step(x, reg):\n"
                      "    return helper(x, reg)\n")
    # H10 capture: mutable instance attr into a jitted method
    w("cap_mod.py", "import jax\n"
                    "class T:\n"
                    "    def __init__(self):\n"
                    "        self.hist = []\n"
                    "    @jax.jit\n"
                    "    def traced(self, x):\n"
                    "        return x + len(self.hist)\n")
    # H11: unclosed ModelServer
    w("srv_mod.py", "class ModelServer:\n"
                    "    def submit(self, x):\n"
                    "        return x\n"
                    "    def close(self):\n"
                    "        pass\n")
    w("leak_mod.py", "from srv_mod import ModelServer\n"
                     "def leaky(x):\n"
                     "    s = ModelServer()\n"
                     "    s.submit(x)\n")
    found = analyze_paths([d], cache_path=None)
    by_rule = {}
    for f in found:
        if not f.suppressed:
            by_rule.setdefault(f.rule, []).append(f)
    h10 = by_rule.get("H10", [])
    assert any("helper_mod:helper" in f.message
               and "metrics_mod:bump" in f.message
               for f in h10), [f.render() for f in h10]
    assert any("self.hist" in f.message for f in h10), \
        [f.render() for f in h10]
    assert any("ModelServer" in f.message
               for f in by_rule.get("H11", [])), by_rule.keys()

# H12: swallowing handler in a serve-scoped module
from sparkdl_tpu.analysis import analyze_source
h12 = [f for f in analyze_source(
    "def dispatch(q):\n"
    "    try:\n"
    "        q.pop()\n"
    "    except Exception:\n"
    "        pass\n", "sparkdl_tpu/serve/fixture.py", rules=["H12"])
    if not f.suppressed]
assert len(h12) == 1, h12
print(json.dumps({"effect_fixtures": "ok",
                  "h10": len(h10), "h11": 1, "h12": 1}))
EOF
# twelve-rule cleanliness is step 6's gate; here: SARIF + fast loop
python -m sparkdl_tpu.analysis --sarif /tmp/sparkdl_lint.sarif \
  sparkdl_tpu tools examples
python - <<'EOF'
import json

with open("/tmp/sparkdl_lint.sarif") as f:
    doc = json.load(f)
assert doc["version"] == "2.1.0", doc.get("version")
assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
[run] = doc["runs"]
rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
assert {"H1", "H10", "H11", "H12", "H14", "H15", "H16"} <= rules, \
    sorted(rules)
for res in run["results"]:
    assert res["ruleId"] in rules
    assert res["message"]["text"]
    [loc] = res["locations"]
    assert loc["physicalLocation"]["region"]["startLine"] >= 1
# the package is lint-clean, so every SARIF result is a suppression
assert all("suppressions" in r for r in run["results"]), \
    [r["ruleId"] for r in run["results"] if "suppressions" not in r]
print(json.dumps({"sarif_gate": "ok",
                  "results": len(run["results"])}))
EOF
tools/lint.sh --fast

echo "== [9/17] fault-drill gate (injected serve-dispatch faults, docs/RESILIENCE.md) =="
SPARKDL_TPU_SLO_WINDOW_S=2 \
  SPARKDL_TPU_FAULTS=serve.dispatch:transient:0.1:1234 \
  python - <<'EOF'
import json
import threading
import time
import urllib.request

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.slo import slo_tracker
from sparkdl_tpu.resilience import faults
from sparkdl_tpu.serve import ModelServer, ServeConfig

assert faults.state()["armed"], "SPARKDL_TPU_FAULTS did not arm"

def apply(params, inputs):
    return {"y": np.asarray(inputs["x"], np.float32) * 2.0}

mf = ModelFunction(apply, None, {"x": ((4,), np.float32)},
                   output_names=["y"], backend="host")
server = ModelServer(ServeConfig(
    max_wait_s=0.001, max_queue_rows=4096,
    dispatch_retries=3, retry_base_backoff_s=0.001))
server.register("drill", mf, batch_size=16)
tel = server.serve_telemetry()

N_THREADS, N_REQ, ROWS = 4, 40, 8
futures, lock = [], threading.Lock()

def fire(tid):
    rng = np.random.default_rng(tid)
    for i in range(N_REQ):
        # unique payload per request: the value IS the identity, so
        # the zero-lost/zero-duplicate check below is exact
        val = float(tid * N_REQ + i)
        x = np.full((ROWS, 4), val, np.float32)
        f = server.submit({"x": x})
        with lock:
            futures.append((val, f))

workers = [threading.Thread(target=fire, args=(t,))
           for t in range(N_THREADS)]
for w in workers: w.start()
for w in workers: w.join()

ok = typed = 0
for val, f in futures:
    try:
        out = f.result(timeout=60)
        assert out["y"].shape == (ROWS, 4), out["y"].shape
        assert np.allclose(out["y"], 2.0 * val), \
            ("row identity corrupted", val, out["y"][0])
        ok += 1
    except Exception:
        typed += 1      # typed failure: resolved, not lost
assert ok + typed == N_THREADS * N_REQ, (ok, typed)
assert ok > 0, "drill lost every request"

snap = default_registry().snapshot()
assert snap.get("faults.injected", 0) > 0, "no faults injected"
assert snap.get("faults.serve.dispatch.injected", 0) > 0, snap
assert snap.get("serve.retries", 0) > 0, \
    "injected transients never exercised the re-dispatch path"

# recovery: disarm, run clean traffic, let the drill window roll off
faults.disarm()
for i in range(10):
    server.submit({"x": np.ones((ROWS, 4), np.float32)}).result(
        timeout=60)
time.sleep(2.2)         # SPARKDL_TPU_SLO_WINDOW_S=2
slo_tracker().record(latency_s=0.001, ok=True)   # roll the window
health = urllib.request.urlopen(tel.url("/healthz"), timeout=5)
assert health.status == 200, health.status
status = json.loads(urllib.request.urlopen(
    tel.url("/statusz"), timeout=5).read())
burn = status["slo"]["objectives"]["availability"]["burn_rate"]
assert burn < 1.0, f"availability burn {burn} still >= 1 after drill"
res = status["resilience"]
assert res["totals"].get("faults.injected", 0) > 0, res
server.close()
print(json.dumps({
    "fault_drill": "ok", "requests": ok + typed, "succeeded": ok,
    "typed_failures": typed,
    "injected": snap["faults.injected"],
    "serve_retries": snap["serve.retries"],
    "availability_burn_after": burn}))
EOF

echo "== [10/17] throughput-hazard gate (H14/H15/H16 fixtures + analyzer cost, docs/LINT.md) =="
python - <<'EOF'
import json
import os
import tempfile

from sparkdl_tpu.analysis import analyze_paths, analyze_source

# seeded fixtures: each throughput rule must CATCH its shape
with tempfile.TemporaryDirectory() as d:
    def w(name, src):
        with open(os.path.join(d, name), "w") as f:
            f.write(src)

    # H14: hot loop (watchdog-marked) doing a per-step .item() sync,
    # with the sync one resolved call away — the witness chain must
    # name both functions
    w("hotsync_mod.py",
      "import jax.numpy as jnp\n"
      "from sparkdl_tpu.obs.watchdog import watch as watchdog_watch\n"
      "def record(loss, out):\n"
      "    out.append(loss.item())\n"
      "def drive(step, batches, out):\n"
      "    for b in batches:\n"
      "        with watchdog_watch('fixture.step'):\n"
      "            loss = jnp.asarray(b)\n"
      "            record(loss, out)\n")
    # H15: undonated jit call whose device batch is dead after it,
    # plus the escaping negative (the result-carrying state is read
    # later, the escaping batch is retained by a list)
    w("donate_mod.py",
      "import jax\n"
      "import jax.numpy as jnp\n"
      "def loop(step, X, keep):\n"
      "    jitted = jax.jit(step)\n"
      "    state = jnp.zeros((4,), jnp.float32)\n"
      "    for i in range(8):\n"
      "        xb = jnp.asarray(X[i])\n"
      "        kept = jnp.asarray(X[i])\n"
      "        keep.append(kept)\n"
      "        state = jitted(state, xb, kept)\n"
      "    return state\n")
    # H16: dtype-less np.zeros mixed into device arithmetic on a hot
    # function
    w("widen_mod.py",
      "import numpy as np\n"
      "import jax.numpy as jnp\n"
      "from sparkdl_tpu.obs.watchdog import watch as watchdog_watch\n"
      "def ship(chunks):\n"
      "    for c in chunks:\n"
      "        with watchdog_watch('fixture.ship'):\n"
      "            dev = jnp.asarray(c)\n"
      "            dev = dev + np.zeros(len(c))\n"
      "    return dev\n")
    found = analyze_paths([d], cache_path=None)
    by_rule = {}
    for f in found:
        if not f.suppressed:
            by_rule.setdefault(f.rule, []).append(f)
    h14 = by_rule.get("H14", [])
    assert any("`.item()`" in f.message and "record" in f.message
               and "drive" in f.message for f in h14), \
        [f.render() for f in h14]
    h15 = by_rule.get("H15", [])
    assert any("`xb`" in f.message and "donate_argnums=(1,)"
               in f.message for f in h15), [f.render() for f in h15]
    # the escaping twin must stay silent — donation of a retained
    # buffer would be a correctness bug, not a perf win
    assert not any("`kept`" in f.message for f in h15), \
        [f.render() for f in h15]
    assert not any("`state`" in f.message for f in h15), \
        [f.render() for f in h15]
    h16 = by_rule.get("H16", [])
    assert any("np.zeros" in f.message and "`dev`" in f.message
               for f in h16), [f.render() for f in h16]

# the sanctioned-drain contract: the same .item() shape inside the
# allowlisted timed_device_get scope reports SUPPRESSED, not silent
drain = analyze_source(
    "import jax.numpy as jnp\n"
    "from sparkdl_tpu.obs.watchdog import watch as watchdog_watch\n"
    "def timed_device_get(res):\n"
    "    with watchdog_watch('drain'):\n"
    "        v = jnp.asarray(res)\n"
    "        return v.item()\n",
    "sparkdl_tpu/obs/trace.py", rules=["H14"])
assert drain and all(f.suppressed for f in drain), \
    [f.render() for f in drain]
print(json.dumps({"throughput_fixtures": "ok",
                  "h14": len(h14), "h15": len(h15),
                  "h16": len(h16)}))
EOF
# analyzer cost guard: the --json timing block must exist with per-rule
# stats, and a WARM cached run (step 7 populated the cache) must hit
# every file — the dataflow facts replay from the cache, nothing
# re-scans — inside a bounded wall time
SPARKDL_TPU_LINT_CACHE=/tmp/sparkdl_lint_ci_cache.json python - <<'EOF'
import json
import os
import subprocess
import sys

env = dict(os.environ)
r = subprocess.run(
    [sys.executable, "-m", "sparkdl_tpu.analysis", "--json",
     "sparkdl_tpu", "tools", "examples"],
    capture_output=True, text=True, env=env)
assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                           r.stderr[-2000:])
d = json.loads(r.stdout)
t = d["timing"]
assert "total_s" in t and "per_rule_s" in t, sorted(t)
for rule in ("H14", "H15", "H16", "H7", "H9", "H10"):
    assert rule in t["per_rule_s"], (rule, sorted(t["per_rule_s"]))
assert d["cache"]["misses"] == 0, \
    ("warm run re-analyzed files", d["cache"])
# the fast-loop bound: a fully-cached whole-package run (facts replay,
# program rules only) must stay interactive — generous for CI hosts,
# tight enough to catch a dataflow closure gone quadratic
assert t["total_s"] < 60.0, t
print(json.dumps({"analyzer_cost_gate": "ok",
                  "warm_total_s": t["total_s"],
                  "h14_s": t["per_rule_s"]["H14"],
                  "h15_s": t["per_rule_s"]["H15"],
                  "h16_s": t["per_rule_s"]["H16"]}))
EOF

echo "== [11/17] live-roofline ledger gate (scrape + bundle) =="
# live scrape + flight bundle: traffic -> a ledger window ->
# /metricsz carries sparkdl_ledger_util_* (with HELP), /statusz and a
# flight dump both carry the ledger section with its history ring.
# The probe file points at a throwaway: this step INJECTS fabricated
# ceilings, which must never land in the host's shared probe cache
# where a later real process would read them as measured bandwidth.
SPARKDL_TPU_FLIGHT_DIR=/tmp \
  SPARKDL_TPU_LEDGER_PROBE_FILE=/tmp/sparkdl_ci_ledger_probe.json \
  python - <<'EOF'
import json
import re
import urllib.request

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import flight, start_telemetry
from sparkdl_tpu.obs.ledger import ledger
from sparkdl_tpu.runtime.runner import BatchRunner

led = ledger()
led.ensure_ceilings({"link_h2d_MBps": 100.0, "link_d2h_MBps": 100.0,
                     "source": "ci-step-11"})
led.baseline()
mf = ModelFunction.fromSingle(lambda x: x * 2.0, None, input_shape=(4,))
runner = BatchRunner(mf, batch_size=8)
runner.run({"input": np.ones((32, 4), np.float32)})
w = led.tick()
assert w is not None and w["util"]["compute"] > 0.0, w

tel = start_telemetry()
with urllib.request.urlopen(tel.url("/metricsz"), timeout=5) as r:
    body = r.read().decode()
for stage in ("decode", "link", "compute", "serve"):
    assert re.search(rf"^sparkdl_ledger_util_{stage} ", body, re.M), \
        f"sparkdl_ledger_util_{stage} missing from /metricsz"
    assert re.search(rf"^# HELP sparkdl_ledger_util_{stage} ", body,
                     re.M), f"HELP missing for ledger.util.{stage}"
assert re.search(r"^sparkdl_ledger_bound_by ", body, re.M), body[:400]

with urllib.request.urlopen(tel.url("/statusz"), timeout=5) as r:
    st = json.load(r)
assert "ledger" in st, sorted(st)
for k in ("window_s", "windows", "history_len", "evicted", "ceilings",
          "last", "history"):
    assert k in st["ledger"], f"/statusz ledger missing {k!r}"
assert st["ledger"]["windows"] >= 1, st["ledger"]
assert isinstance(st["ledger"]["history"], list) \
    and st["ledger"]["history"], "empty ledger history on /statusz"

path = flight.recorder().dump(reason="ci ledger gate")
with open(path) as f:
    bundle = json.load(f)
assert "ledger" in bundle, sorted(bundle)
assert isinstance(bundle["ledger"].get("history"), list) \
    and bundle["ledger"]["history"], bundle["ledger"]
assert bundle["ledger"]["history"][-1]["bound_by"] in (
    "decode", "link", "compute", "serve", "idle"), bundle["ledger"]
tel.close()
print(json.dumps({"ledger_scrape_gate": "ok",
                  "bound_by": w["bound_by"],
                  "windows": st["ledger"]["windows"],
                  "bundle": path}))
EOF

echo "== [12/17] compile-forensics gate (injected retrace drill + report --compile) =="
# (a) the enforcement drill: a warmed serve soak must stay at ZERO
# unexpected retraces; an injected off-ladder shape must then show
# compile.unexpected_retraces > 0 with the diff naming the changed
# argument, a flight dump carrying the attribution, and the /healthz
# detail flipped — and the drill's armed trace feeds the CLI smoke
SPARKDL_TPU_FLIGHT_DIR=/tmp python - <<'EOF'
import json
import re
import urllib.request

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry, flight, start_telemetry
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.serve import ModelServer, ServeConfig

clog = compile_log()
clog.arm()
tracer().arm()
flight.recorder().arm()
reg = default_registry()

mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                              input_shape=(4,), name="ci_drill")
server = ModelServer(ServeConfig(max_wait_s=0.01))
session = server.register("drill", mf, batch_size=8)
warmed = server.warmup()
assert warmed == {"drill": True}, warmed
base = reg.counter("compile.unexpected_retraces").value

# the steady-state soak: warmed-shape traffic compiles NOTHING
x = np.ones((4, 4), np.float32)
for _ in range(8):
    server.submit({"input": x}).result(timeout=60)
assert reg.counter("compile.unexpected_retraces").value == base, \
    "clean warmed soak must report zero unexpected retraces"

# the injection: the runner's device batch moved off the warmed shape
dumps_before = flight.recorder().dumps
session.runner.batch_size = 6
server.submit({"input": np.ones((8, 4), np.float32)}
              ).result(timeout=60)
server.close()
assert reg.counter("compile.unexpected_retraces").value > base, \
    "injected off-ladder shape did not count an unexpected retrace"
ev = [e for e in clog.events() if e.unexpected][-1]
assert ev.diff and "inputs.input" in ev.diff, ev.diff
assert "float32[8,4]" in ev.diff and "float32[6,4]" in ev.diff, \
    ev.diff

# the flight dump fired with the attribution aboard
assert flight.recorder().dumps == dumps_before + 1, \
    (flight.recorder().dumps, dumps_before)
with open(flight.recorder().last_dump_path) as f:
    bundle = json.load(f)
assert "unexpected retrace" in bundle["reason"], bundle["reason"]
assert bundle["compile"]["unexpected_retraces"] >= 1
assert any(r.get("unexpected") and r.get("diff")
           for r in bundle["compile"]["recent"]), bundle["compile"]

# /healthz detail flips (status stays the watchdog's), /statusz and
# /metricsz carry the compile + hbm surfaces
tel = start_telemetry()
with urllib.request.urlopen(tel.url("/healthz"), timeout=5) as r:
    hz = json.load(r)
assert hz["unexpected_retraces"] >= 1, hz
assert hz["compile_steady"] is False, hz
with urllib.request.urlopen(tel.url("/statusz"), timeout=5) as r:
    st = json.load(r)
assert st["compile"]["unexpected_retraces"] >= 1, st["compile"]
assert "ci_drill.jitted" in st["compile"]["functions"], \
    sorted(st["compile"]["functions"])
with urllib.request.urlopen(tel.url("/metricsz"), timeout=5) as r:
    body = r.read().decode()
assert re.search(r"^sparkdl_compile_unexpected_retraces ", body,
                 re.M), body[:400]
assert re.search(r"^# HELP sparkdl_compile_unexpected_retraces ",
                 body, re.M)
assert re.search(r"^sparkdl_hbm_devices_reporting ", body, re.M), \
    "hbm accounting missing from /metricsz"
tel.close()

tracer().export("/tmp/sparkdl_ci_compile_trace.json")
print(json.dumps({"retrace_drill": "ok", "diff": ev.diff[:120],
                  "bundle": flight.recorder().last_dump_path}))
EOF
# (b) the offline CLI reads the drill's trace: compile counts per
# function + the retrace diffs, the UNEXPECTED one flagged
python -m sparkdl_tpu.obs report --compile \
  /tmp/sparkdl_ci_compile_trace.json | tee /tmp/sparkdl_compile_report.txt
grep -q "compile forensics" /tmp/sparkdl_compile_report.txt
grep -q "UNEXPECTED" /tmp/sparkdl_compile_report.txt
grep -q "ci_drill.jitted" /tmp/sparkdl_compile_report.txt

echo "== [13/17] parallel host pipeline gate (overlap drill + ordered re-merge + watchdog, docs/PERFORMANCE.md) =="
# the overlap drill (>= 2 cores only): a decode-heavy plan on the
# PROCESS pool must earn (decode_busy)/wall > 1.1 — only possible when
# partitions genuinely run concurrently; plus the ordered re-merge,
# row-identity, watchdog-stall, convergence, and surface gates, which
# run pooled on ANY host (explicit modes bypass the 1-core degrade).
SPARKDL_TPU_PIPELINE_MPCTX=fork SPARKDL_TPU_FLIGHT_DIR=/tmp python - <<'EOF'
import json
import os
import threading
import time

import numpy as np
import pyarrow as pa

from sparkdl_tpu.data import DataFrame, LocalEngine
from sparkdl_tpu.data import pipeline as host_pipeline
from sparkdl_tpu.obs import default_registry, flight
from sparkdl_tpu.obs.watchdog import watchdog

reg = default_registry()
cores = os.cpu_count() or 1


def ids_df(ids, parts, engine):
    return DataFrame(
        DataFrame.from_table(pa.table({"id": ids}), parts)._sources,
        engine=engine)


# -- overlap proof (process pool, decode-heavy stage) ----------------
if cores >= 2:
    eng = LocalEngine(pipeline_workers=2, pipeline_mode="process")

    def burn(batch):
        # a CPU-heavy pure-Python "decode": the GIL would serialize
        # this on threads — exactly what the process pool exists for
        acc = 0
        deadline = time.perf_counter() + 0.15
        while time.perf_counter() < deadline:
            acc += 1
        return batch

    ids = np.arange(80)
    busy0 = reg.counter("engine.busy_seconds").value
    t0 = time.perf_counter()
    out = ids_df(ids, 8, eng).map_batches(burn, name="burn").collect()
    wall = time.perf_counter() - t0
    busy = reg.counter("engine.busy_seconds").value - busy0
    np.testing.assert_array_equal(
        out.column("id").to_numpy(zero_copy_only=False), ids)
    ratio = busy / max(wall, 1e-9)
    assert ratio > 1.1, \
        (f"no decode overlap on a {cores}-core host: busy {busy:.3f}s "
         f"over wall {wall:.3f}s = {ratio:.2f}")
    eng.shutdown()
else:
    ratio = None

# -- ordered re-merge: zero lost/duplicated rows by identity ---------
eng = LocalEngine(pipeline_workers=3, pipeline_mode="thread")


def jitter(batch, idx):
    time.sleep(0.02 * ((idx * 7) % 5) / 5)   # adversarial completion
    return batch


ids = np.arange(120)
out = ids_df(ids, 10, eng).map_batches(
    jitter, with_index=True, name="jitter").collect()
got = out.column("id").to_numpy(zero_copy_only=False)
assert len(got) == len(ids) and len(set(got.tolist())) == len(ids), \
    "pooled path lost or duplicated rows"
np.testing.assert_array_equal(got, ids)

# -- watchdog fed per worker: injected stall fires, names, recovers --
wd = watchdog()
wd.arm(threshold_s=0.2)
stalls0 = reg.counter("watchdog.stalls").value
recov0 = reg.counter("watchdog.recoveries").value
stalled_names = []


def sample():
    deadline = time.perf_counter() + 8.0
    while time.perf_counter() < deadline:
        v = wd.verdict()
        if v["stalled_sources"]:
            stalled_names.extend(v["stalled_sources"])
            return
        time.sleep(0.02)


def wedge(batch, idx):
    if idx == 1:
        time.sleep(0.8)                     # > threshold: the stall
    return batch


sampler = threading.Thread(target=sample)
sampler.start()
out = ids_df(ids, 3, eng).map_batches(
    wedge, with_index=True, name="wedge").collect()
sampler.join(10.0)
assert out.num_rows == 120
assert reg.counter("watchdog.stalls").value > stalls0, \
    "injected stalled worker fired no watchdog stall"
assert any(s.startswith("pipeline.decode:") for s in stalled_names), \
    f"stall did not name the pipeline source: {stalled_names}"
assert wd.healthy(), "stall did not recover after completion"
assert reg.counter("watchdog.recoveries").value > recov0
wd.disarm()
wd.arm_from_env()

# -- PipelineTarget convergence: zero oscillations -------------------
from sparkdl_tpu.autotune import PipelineTarget
from sparkdl_tpu.autotune.core import AutotuneController

ctl = AutotuneController(interval_s=0.0)
ctl.arm(interval_s=0.0)
target = PipelineTarget(eng, max_workers=4)
target._ledger_prior = lambda: "decode"     # pin the prior for determinism
ctl.attach(target)
osc0 = reg.counter("autotune.oscillations").value
for _ in range(12):
    ids_df(np.arange(30), 3, eng).map_batches(lambda b: b).collect()
    ctl.step()
assert ctl.oscillations == 0, ctl.state()
assert reg.counter("autotune.oscillations").value == osc0
assert 1 <= eng.pipeline_workers <= 4, eng.pipeline_workers
knobs = {k["name"]: k for k in target.describe()["knobs"]}
assert set(knobs) == {"pipeline_workers", "pipeline_read_ahead"}
ctl.reset()

# -- live values ride /statusz and flight bundles --------------------
import urllib.request

from sparkdl_tpu.obs import start_telemetry

tel = start_telemetry()
with urllib.request.urlopen(tel.url("/statusz"), timeout=5) as r:
    st = json.load(r)
assert "pipeline" in st, sorted(st)
for k in ("mode", "workers", "read_ahead", "counters"):
    assert k in st["pipeline"], f"/statusz pipeline missing {k!r}"
assert "pipeline.tasks" in st["pipeline"]["counters"], \
    sorted(st["pipeline"]["counters"])
with urllib.request.urlopen(tel.url("/metricsz"), timeout=5) as r:
    body = r.read().decode()
import re
assert re.search(r"^sparkdl_pipeline_tasks ", body, re.M), body[:400]
assert re.search(r"^# HELP sparkdl_pipeline_tasks ", body, re.M)
tel.close()
path = flight.recorder().dump(reason="ci pipeline gate")
with open(path) as f:
    bundle = json.load(f)
assert "pipeline" in bundle, sorted(bundle)
assert bundle["pipeline"]["mode"] in ("thread", "process"), \
    bundle["pipeline"]
eng.shutdown()
print(json.dumps({"pipeline_gate": "ok", "cores": cores,
                  "drill_overlap_ratio":
                      round(ratio, 3) if ratio else None,
                  "stalled_sources": stalled_names[:3],
                  "bundle": path}))
EOF

echo "== [14/17] static-race gate (H17/H18/H19 fixtures + witness content + nineteen-rule SARIF, docs/LINT.md) =="
python - <<'EOF'
import json
import os
import tempfile

from sparkdl_tpu.analysis import analyze_paths, to_sarif
from sparkdl_tpu.analysis.walker import ALL_RULES

assert len(ALL_RULES) == 19, sorted(ALL_RULES)

RACY = (
    "import threading\n"
    "\n"
    "class Buf:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.items = []\n"
    "    def start(self):\n"
    "        threading.Thread(target=self.worker).start()\n"
    "    def worker(self):\n"
    "        with self._lock:\n"
    "            self.items.append(1)\n"
    "    def size(self):\n"
    "        with self._lock:\n"
    "            return len(self.items)\n"
    "    def peek(self):\n"
    "        return self.items[0]\n")

HANDOFF = (
    "import threading\n"
    "\n"
    "def worker(buf):\n"
    "    buf.append(1)\n"
    "\n"
    "def main():\n"
    "    buf = []\n"
    "    t = threading.Thread(target=worker, args=(buf,))\n"
    "    t.start()\n"
    "    buf.append(2)\n")

SPLIT = (
    "import threading\n"
    "\n"
    "class Q:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.rows = []\n"
    "        self.cap = 4\n"
    "    def start(self):\n"
    "        threading.Thread(target=self.drain).start()\n"
    "    def drain(self):\n"
    "        with self._lock:\n"
    "            if self.rows:\n"
    "                self.rows.pop()\n"
    "    def offer(self, row):\n"
    "        with self._lock:\n"
    "            if len(self.rows) >= self.cap:\n"
    "                return False\n"
    "        with self._lock:\n"
    "            self.rows.append(row)\n"
    "        return True\n")

with tempfile.TemporaryDirectory() as d:
    for name, src in (("racy.py", RACY), ("handoff.py", HANDOFF),
                      ("split.py", SPLIT)):
        with open(os.path.join(d, name), "w") as f:
            f.write(src)
    found = analyze_paths([d], cache_path=None)
    by_rule = {}
    for f in found:
        if not f.suppressed:
            by_rule.setdefault(f.rule, []).append(f)
    # H17: the full guarded-by witness — verb, lock identity, vote,
    # BOTH thread roots (spawned + implicit main)
    h17 = [f for f in by_rule.get("H17", [])
           if f.qualname == "Buf.peek"]
    assert h17, [f.render() for f in by_rule.get("H17", [])]
    msg = h17[0].message
    for needle in ("read without holding", "Buf._lock",
                   "majority evidence", "the main thread",
                   "instance state"):
        assert needle in msg, (needle, msg)
    # H18: the hand-off witness — the local, the boundary kind, both
    # sides' mutation sites
    h18 = by_rule.get("H18", [])
    assert any("mutable local `buf`" in f.message
               and "a thread target" in f.message
               and "`buf` parameter" in f.message
               for f in h18), [f.render() for f in h18]
    # H19: the split witness — both hold lines, the TOCTOU verdict
    h19 = by_rule.get("H19", [])
    assert any("check-then-act split on `self.rows`" in f.message
               and "SEPARATE hold" in f.message
               and "TOCTOU" in f.message
               for f in h19), [f.render() for f in h19]

# the negatives: locking every access, keeping check+act in ONE
# hold, and double-checked locking must all stay silent
with tempfile.TemporaryDirectory() as d:
    safe_racy = RACY.replace(
        "    def peek(self):\n"
        "        return self.items[0]\n",
        "    def peek(self):\n"
        "        with self._lock:\n"
        "            return self.items[0]\n")
    safe_split = SPLIT.replace(
        "        with self._lock:\n"
        "            self.rows.append(row)\n",
        "        with self._lock:\n"
        "            if len(self.rows) < self.cap:\n"
        "                self.rows.append(row)\n")
    for name, src in (("safe_racy.py", safe_racy),
                      ("safe_split.py", safe_split)):
        with open(os.path.join(d, name), "w") as f:
            f.write(src)
    found = analyze_paths([d], rules=["H17", "H18", "H19"],
                          cache_path=None)
    unsup = [f for f in found if not f.suppressed]
    assert unsup == [], [f.render() for f in unsup]

# SARIF: well-formed 2.1.0 with ALL nineteen rules in the driver
sarif = to_sarif([], rules=ALL_RULES)
json.dumps(sarif)                      # must round-trip as JSON
assert sarif["$schema"].endswith("sarif-schema-2.1.0.json")
rules = {r["id"] for r in sarif["runs"][0]["tool"]["driver"]["rules"]}
assert len(rules & set(ALL_RULES)) == 19, sorted(rules)
assert {"H17", "H18", "H19"} <= rules, sorted(rules)
print(json.dumps({"race_fixtures": "ok",
                  "sarif_rules": len(rules)}))
EOF
# the warm acceptance pass: with the cache populated by steps 11/14,
# the nineteen-rule sweep over package + tools + examples must hit
# every file, stay clean, keep the race passes in the timing block,
# and stay inside the interactive bound
SPARKDL_TPU_LINT_CACHE=/tmp/sparkdl_lint_ci_cache.json python - <<'EOF'
import json
import os
import subprocess
import sys

env = dict(os.environ)
r = subprocess.run(
    [sys.executable, "-m", "sparkdl_tpu.analysis", "--json",
     "sparkdl_tpu", "tools", "examples"],
    capture_output=True, text=True, env=env)
assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                           r.stderr[-2000:])
d = json.loads(r.stdout)
assert d["unsuppressed"] == 0, d["unsuppressed"]
assert d["cache"]["misses"] == 0, \
    ("warm run re-analyzed files", d["cache"])
t = d["timing"]
for key in ("H17", "H18", "H19", "threads-topology"):
    assert key in t["per_rule_s"], (key, sorted(t["per_rule_s"]))
assert t["total_s"] < 60.0, t
print(json.dumps({"race_gate": "ok",
                  "warm_total_s": t["total_s"],
                  "h17_s": t["per_rule_s"]["H17"],
                  "h18_s": t["per_rule_s"]["H18"],
                  "h19_s": t["per_rule_s"]["H19"],
                  "topology_s": t["per_rule_s"]["threads-topology"]}))
EOF

echo "== [15/17] cross-process telemetry gate (merged worker trace + scrape + fault/death drills + report --workers, docs/OBSERVABILITY.md) =="
SPARKDL_TPU_PIPELINE_MPCTX=fork SPARKDL_TPU_TRACE=1 \
  SPARKDL_TPU_FLIGHT=1 SPARKDL_TPU_FLIGHT_DIR=/tmp python - <<'EOF'
import json
import os
import re
import urllib.request

import numpy as np
import pyarrow as pa

from sparkdl_tpu.data import DataFrame, LocalEngine
from sparkdl_tpu.data.pipeline import PipelineWorkerError
from sparkdl_tpu.obs import default_registry, start_telemetry
from sparkdl_tpu.obs import remote
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.resilience import faults

reg = default_registry()
agg = remote.aggregator()


def ids_df(ids, parts, engine):
    return DataFrame(
        DataFrame.from_table(pa.table({"id": ids}), parts)._sources,
        engine=engine)


# -- (a) armed pooled stream -> ONE merged, clock-aligned trace ------
eng = LocalEngine(pipeline_workers=2, pipeline_mode="process")
ids = np.arange(160)
out = ids_df(ids, 4, eng).map_batches(lambda b: b).collect()
np.testing.assert_array_equal(
    out.column("id").to_numpy(zero_copy_only=False), ids)
assert agg.health()["workers"] >= 1, agg.health()
trace_path = "/tmp/sparkdl_ci_worker_trace.json"
tracer().export(trace_path)
with open(trace_path) as f:
    events = json.load(f)
worker_pids = sorted({e["pid"] for e in events
                      if e["pid"] >= remote.WORKER_PID_BASE})
assert worker_pids, "merged trace has no worker process tracks"
procs = {e["pid"]: e["args"]["name"] for e in events
         if e["ph"] == "M" and e["name"] == "process_name"}
for pid in worker_pids:
    assert procs.get(pid, "").startswith("worker."), (pid, procs)
wx = [e for e in events if e["ph"] == "X"
      and e["pid"] >= remote.WORKER_PID_BASE]
px = [e for e in events if e["ph"] == "X"
      and e["pid"] < remote.WORKER_PID_BASE]
names = {e["name"] for e in wx}
assert "worker.decode" in names, sorted(names)
# time alignment: every worker span inside the parent stream's
# window (generous slack for the handshake's clock sampling skew)
pmin = min(e["ts"] for e in px)
pmax = max(e["ts"] + e["dur"] for e in px)
slack = 0.5e6
for e in wx:
    assert pmin - slack <= e["ts"] <= pmax + slack, \
        (e["name"], e["ts"], pmin, pmax)

# -- (b) sparkdl_worker_* on a live scrape, with # HELP --------------
tel = start_telemetry()
with urllib.request.urlopen(tel.url("/metricsz"), timeout=5) as r:
    body = r.read().decode()
assert re.search(r"^sparkdl_worker_", body, re.M), \
    "no sparkdl_worker_* series on /metricsz"
assert re.search(r"^# HELP sparkdl_worker_", body, re.M), \
    "sparkdl_worker_* series scraped without # HELP"
tel.close()

# -- (c) injected worker-side transient fault: retried, counted, ----
# zero lost rows (the spec ships via the telemetry config)
faults.inject("pipeline.worker_decode", "transient", 0.3, seed=7)
injected0 = reg.counter(
    "worker.all.faults.pipeline.worker_decode.injected").value
retries0 = reg.counter("engine.retries").value
ids2 = np.arange(240)
out2 = ids_df(ids2, 6, eng).map_batches(lambda b: b).collect()
faults.disarm()
np.testing.assert_array_equal(
    out2.column("id").to_numpy(zero_copy_only=False), ids2)
injected = reg.counter(
    "worker.all.faults.pipeline.worker_decode.injected").value
assert injected > injected0, \
    "worker-side fault counters never reached the parent registry"
assert reg.counter("engine.retries").value > retries0, \
    "injected worker fault produced no parent-side retry"
eng.shutdown()

# -- (d) worker-death drill: a REAL corpse, named in the bundle ------
eng2 = LocalEngine(pipeline_workers=2, pipeline_mode="process")
# one clean stream first: the aggregator learns the fresh pool's pids
# (a worker that dies on its FIRST task never ships a frame — death
# attribution probes the pids the plane has seen)
ids_df(np.arange(40), 4, eng2).map_batches(lambda b: b).collect()
faults.inject("pipeline.worker_death", "transient", 1.0, seed=1)
deaths0 = reg.counter("pipeline.worker_deaths").value
err = None
try:
    ids_df(np.arange(40), 2, eng2).map_batches(lambda b: b).collect()
except PipelineWorkerError as exc:
    err = exc
finally:
    faults.disarm()
    eng2.shutdown()
assert err is not None, "worker death surfaced no PipelineWorkerError"
assert reg.counter("pipeline.worker_deaths").value > deaths0, \
    "worker death not counted as pipeline.worker_deaths"
dead = agg.health()["dead"]
assert dead, "aggregator marked no worker dead after the drill"
bundles = sorted((p for p in os.listdir("/tmp")
                  if p.startswith("sparkdl_flight_")),
                 key=lambda p: os.path.getmtime(os.path.join("/tmp", p)))
assert bundles, "worker death dumped no flight bundle"
with open(os.path.join("/tmp", bundles[-1])) as f:
    bundle = json.load(f)
assert "workers" in bundle, sorted(bundle)
dead_rows = [w for w in bundle["workers"] if w.get("dead")]
assert dead_rows, \
    "flight bundle workers[] names no dead worker"

# -- (e) report --workers reads the merged trace + bundle join -------
import subprocess
import sys
bundle_path = os.path.join("/tmp", bundles[-1])
r = subprocess.run(
    [sys.executable, "-m", "sparkdl_tpu.obs", "report", "--workers",
     "--bundle", bundle_path, trace_path],
    capture_output=True, text=True)
assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
assert "worker.0" in r.stdout, r.stdout[-2000:]
print(json.dumps({
    "telemetry_gate": "ok",
    "worker_tracks": len(worker_pids),
    "worker_spans": len(wx),
    "faults_mirrored": injected - injected0,
    "dead_workers": dead,
    "bundle": bundle_path,
}))
EOF

echo "== [16/17] input-service gate (two-process decode fleet + snapshot tier, docs/DATA_SERVICE.md) =="
python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

import pyarrow as pa
import pyarrow.compute as pc

from sparkdl_tpu.data.engine import LocalEngine
from sparkdl_tpu.data.frame import DataFrame
from sparkdl_tpu.inputsvc import transport as isvc_transport
from sparkdl_tpu.inputsvc import client as isvc_client
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.ledger import UtilizationLedger
from sparkdl_tpu.resilience import faults

reg = default_registry()
N, PARTS = 4096, 8
table = pa.table({
    "id": pa.array(range(N), type=pa.int64()),
    "x": pa.array([float(i % 997) for i in range(N)],
                  type=pa.float64()),
})


def plan(df):
    def work(batch):
        i = batch.schema.get_field_index("x")
        col = batch.column("x")
        for _ in range(40):                # real decode-side work
            col = pc.add(pc.multiply(col, 1.0000001), 0.5)
        return batch.set_column(i, "x", col)
    return df.map_batches(work, name="ci_decode")


def collect_ids(engine):
    out = plan(DataFrame.from_table(table, PARTS, engine)).collect()
    return sorted(out.column("id").to_pylist()), out


# -- (a) spawn THE OTHER PROCESS: one DecodeServer over the CLI ------
proc = subprocess.Popen(
    [sys.executable, "-m", "sparkdl_tpu.inputsvc", "serve",
     "--port", "0"],
    stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
endpoint = None
deadline = time.time() + 90
while time.time() < deadline:
    line = proc.stdout.readline()
    if "SPARKDL_TPU_INPUTSVC READY" in line:
        endpoint = line.strip().rsplit(" ", 1)[-1]
        break
assert endpoint, "DecodeServer CLI never printed its READY line"
assert isvc_transport.parse_endpoint(endpoint) is not None, endpoint

expected = list(range(N))
serial_engine = LocalEngine(num_workers=0)
ids, _ = collect_ids(serial_engine)
assert ids == expected
t0 = time.perf_counter()
collect_ids(serial_engine)
serial_ips = N / (time.perf_counter() - t0)
serial_engine.shutdown()

# -- (b) zero lost/dup rows under 10% inputsvc.rpc injection, with
#        the ledger's decode ceiling scaled by the live remote fleet
#        (two client lanes into the one server process) -------------
led = UtilizationLedger(window_s=1.0, history=4)
led.ensure_ceilings({"link_h2d_MBps": 1.0, "link_d2h_MBps": 1.0,
                     "source": "ci"})
led.baseline()
# seed 2 fires twice in the first 8 draws at rate 0.1 — the drill
# must actually inject on this corpus's 8 fragments
faults.inject("inputsvc.rpc", "transient", 0.1, seed=2)
engine = LocalEngine(inputsvc_endpoints=[endpoint, endpoint])
try:
    inj0 = reg.counter("faults.inputsvc.rpc.injected").value
    rows0 = reg.counter("inputsvc.rows").value
    ids, _ = collect_ids(engine)
finally:
    faults.disarm()
injected = reg.counter("faults.inputsvc.rpc.injected").value - inj0
remote_rows = reg.counter("inputsvc.rows").value - rows0
assert ids == expected, "rows lost or duplicated under the rpc drill"
assert injected > 0, "the 10% drill injected nothing on 8 fragments"
assert remote_rows == N, (remote_rows, N)
w = led.tick()
assert w is not None
assert w["decode_workers"] >= 2, \
    f"ledger decode ceiling not scaled by the remote fleet: {w['decode_workers']}"

# -- (c) kill the worker process: LOUD failover to local decode ------
proc.terminate()
proc.wait(timeout=30)
fb0 = reg.snapshot().get("inputsvc.fallbacks", 0)
ld0 = reg.snapshot().get("inputsvc.local_decodes", 0)
ids, _ = collect_ids(engine)
engine.shutdown()
assert ids == expected, "rows wrong after worker death"
snap = reg.snapshot()
loud = (snap.get("inputsvc.fallbacks", 0) - fb0) + \
    (snap.get("inputsvc.local_decodes", 0) - ld0)
assert loud > 0, "worker death failed over silently (nothing counted)"

# -- (d) snapshot tier: second epoch decodes ~nothing, streams at
#        >= the serial-decode baseline ------------------------------
snap_root = tempfile.mkdtemp(prefix="sparkdl_ci_snap_")
snap_engine = LocalEngine(num_workers=0)
try:
    base = plan(DataFrame.from_table(table, PARTS, snap_engine))
    cold = base.snapshot(snap_root, fingerprint="ci-corpus")
    out = cold.collect()
    assert sorted(out.column("id").to_pylist()) == expected
    assert reg.snapshot().get("inputsvc.snapshot_writes", 0) >= PARTS

    warm_ips = 0.0
    busy0 = reg.counter("engine.busy_seconds").value
    for _ in range(2):
        warm = base.snapshot(snap_root, fingerprint="ci-corpus")
        t0 = time.perf_counter()
        out = warm.collect()
        warm_ips = max(warm_ips, N / (time.perf_counter() - t0))
    warm_busy = reg.counter("engine.busy_seconds").value - busy0
    assert sorted(out.column("id").to_pylist()) == expected
    assert warm_busy < 0.05, \
        f"warm epoch still decoding: busy {warm_busy:.4f}s"
    assert warm_ips >= serial_ips, \
        f"warm snapshot epoch ({warm_ips:.0f} rows/s) lost to the " \
        f"serial-decode baseline ({serial_ips:.0f} rows/s)"
finally:
    snap_engine.shutdown()
    shutil.rmtree(snap_root, ignore_errors=True)

print(json.dumps({
    "input_service_gate": "ok",
    "rows": N,
    "rpc_faults_injected": int(injected),
    "ledger_decode_workers": int(w["decode_workers"]),
    "loud_failover_events": int(loud),
    "serial_ips": round(serial_ips, 1),
    "snapshot_warm_ips": round(warm_ips, 1),
    "snapshot_warm_decode_busy_s": round(warm_busy, 4),
}))
EOF

echo "== [17/17] fleet gate (hot-swap under load + corrupt-cache fail-closed + cross-process scale-out, docs/SERVING.md) =="
FLEET_CACHE="$(mktemp -d /tmp/sparkdl_ci_fleet.XXXXXX)"
trap 'rm -rf "$FLEET_CACHE"' EXIT
SPARKDL_TPU_FLEET_CACHE="$FLEET_CACHE" python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from sparkdl_tpu.fleet import ModelRegistry, WarmStartCache
from sparkdl_tpu.fleet.warmstart import BLOB_NAME
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.serve import (ModelServer, ServeConfig,
                               ServerOverloaded)

reg_obs = default_registry()
clog = compile_log()
clog.arm()
cache_root = os.environ["SPARKDL_TPU_FLEET_CACHE"]
DIM, BATCH = 8, 16
x = np.ones((BATCH, DIM), np.float32)


def apply(params, inputs):
    return {"y": inputs["x"] @ params["w"]}


def fresh_mf(name, scale):
    return ModelFunction(
        apply, {"w": (scale * np.eye(DIM)).astype(np.float32)},
        {"x": ((DIM,), np.float32)}, ["y"], name=name)


# -- (a) hot-swap under concurrent submit load ----------------------
# cold deploy first (no warmup, empty cache): the first request pays
# the jit compile — that wall is the band the scale-out proof in (c)
# must beat — and the deploy persists the AOT blob for (b) and (c)
cache = WarmStartCache(cache_root)
server = ModelServer(ServeConfig(max_wait_s=0.0))
registry = ModelRegistry(server, warmstart=cache)
registry.deploy("cigate", fresh_mf("cigate", 2.0),
                batch_size=BATCH, replicas=1, warmup=False)
t0 = time.perf_counter()
y = np.asarray(registry.submit({"x": x}, model="cigate"
                               ).result()["y"])
cold_ms = (time.perf_counter() - t0) * 1000.0
assert float(y[0, 0]) == 2.0, y[0, 0]
assert cache.writes >= 1, "cold deploy persisted no AOT blob"
# replica r1 warm-starts from the blob the deploy just wrote
registry.scale("cigate", 2)

retraces0 = clog.unexpected_retraces
compiles0 = (clog.compiles_of("cigate@r0.jitted")
             + clog.compiles_of("cigate@r1.jitted"))
results, lock = [], threading.Lock()
stop = threading.Event()


def fire():
    while not stop.is_set():
        try:
            f = registry.submit({"x": x}, model="cigate")
        except ServerOverloaded:
            time.sleep(0.001)   # admission backpressure — typed,
            continue            # never a dropped future
        with lock:
            results.append(f)


threads = [threading.Thread(target=fire) for _ in range(4)]
for t in threads:
    t.start()
try:
    version = registry.swap_weights(
        "cigate", {"w": (3.0 * np.eye(DIM)).astype(np.float32)},
        note="ci step 17 under load")
finally:
    stop.set()
    for t in threads:
        t.join()
assert version.version == 2
assert results, "the load threads submitted nothing"
for f in results:                    # ZERO dropped: every future resolves
    out = np.asarray(f.result()["y"])
    v = float(out[0, 0])
    assert v in (2.0, 3.0), f"torn output {v}"
    np.testing.assert_allclose(out, v * x)   # never a mixed batch
y = np.asarray(registry.submit({"x": x}, model="cigate"
                               ).result()["y"])
assert float(y[0, 0]) == 3.0, \
    "fleet still serving OLD weights after the swap"
swap_retraces = clog.unexpected_retraces - retraces0
steady_compiles = (clog.compiles_of("cigate@r0.jitted")
                   + clog.compiles_of("cigate@r1.jitted")) - compiles0
assert swap_retraces == 0, f"swap retraced: {swap_retraces}"
assert steady_compiles == 0, \
    f"swap recompiled the steady replicas: {steady_compiles}"
swap_ms = registry.state()["last_swap_ms"]
server.close()

# -- (b) corrupt-cache fail-closed ----------------------------------
# flip the last payload byte of the persisted blob: the next deploy
# must COUNT the corruption, delete the bad blob, compile cold, and
# still answer correctly (then re-persist a healthy blob for (c))
blobs = [os.path.join(cache_root, d, BLOB_NAME)
         for d in os.listdir(cache_root)
         if os.path.exists(os.path.join(cache_root, d, BLOB_NAME))]
assert blobs, f"no AOT blob under {cache_root}"
with open(blobs[0], "r+b") as f:
    f.seek(-1, os.SEEK_END)
    last = f.read(1)[0]
    f.seek(-1, os.SEEK_END)
    f.write(bytes([last ^ 0xFF]))
corrupt0 = reg_obs.counter("fleet.warmstart_corruptions").value
cache2 = WarmStartCache(cache_root)
server2 = ModelServer(ServeConfig(max_wait_s=0.0))
registry2 = ModelRegistry(server2, warmstart=cache2)
registry2.deploy("cigate2", fresh_mf("cigate2", 4.0),
                 batch_size=BATCH, replicas=1, warmup=False)
y = np.asarray(registry2.submit({"x": x}, model="cigate2"
                                ).result()["y"])
assert float(y[0, 0]) == 4.0, \
    "wrong output after the corrupt-cache cold fallback"
corruptions = (reg_obs.counter("fleet.warmstart_corruptions").value
               - corrupt0)
assert corruptions >= 1, "corrupt blob went uncounted"
assert cache2.hits == 0, "corrupt blob counted as a warm HIT"
# fail-CLOSED: the corrupt executable must never be installed — zero
# aot_load events for the fallback replica (it went through the
# normal jit path instead; XLA may dedupe the actual recompile
# against this process's identical earlier program, so the INSTALL
# count, not the compile count, is the load-bearing proof)
assert clog.compiles_of("cigate2@r0.jitted.aot_load") == 0, \
    "a corrupt blob was INSTALLED as an executable"
# the fallback deploy re-persisted a healthy blob — self-healed
assert cache2.writes >= 1, "store did not self-heal after corruption"
server2.close()

# -- (c) scale-out proof: a FRESH process starts warm ---------------
# TWO children, identical but for the cache env: both pay the same
# fresh-process fixed costs (backend init, first dispatch, params
# device_put), so their first-request delta isolates exactly what
# the persisted cache is supposed to delete — the compile
child_src = r"""
import jax
jax.config.update("jax_platforms", "cpu")
import json
import time

import numpy as np

from sparkdl_tpu.fleet import ModelRegistry, WarmStartCache
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.serve import ModelServer, ServeConfig

clog = compile_log()
clog.arm()
DIM, BATCH = 8, 16


def apply(params, inputs):
    return {"y": inputs["x"] @ params["w"]}


mf = ModelFunction(
    apply, {"w": (7.0 * np.eye(DIM)).astype(np.float32)},
    {"x": ((DIM,), np.float32)}, ["y"], name="scaleout")
server = ModelServer(ServeConfig(max_wait_s=0.0))
cache = WarmStartCache()        # root from SPARKDL_TPU_FLEET_CACHE
registry = ModelRegistry(server, warmstart=cache)
registry.deploy("scaleout", mf, batch_size=BATCH, replicas=1,
                warmup=False)
x = np.ones((BATCH, DIM), np.float32)
t0 = time.perf_counter()
y = np.asarray(registry.submit({"x": x}).result()["y"])
first_ms = (time.perf_counter() - t0) * 1000.0
assert float(y[0, 0]) == 7.0, y[0, 0]
print(json.dumps({
    "compiles": clog.compiles_of("scaleout@r0.jitted"),
    "aot_loads": clog.compiles_of("scaleout@r0.jitted.aot_load"),
    "warm_hits": cache.hits,
    "first_request_ms": round(first_ms, 3),
}))
server.close()
"""
def run_child(with_cache):
    env = {k: v for k, v in os.environ.items()
           if k != "SPARKDL_TPU_FLEET_CACHE"}
    if with_cache:
        env["SPARKDL_TPU_FLEET_CACHE"] = cache_root
    r = subprocess.run([sys.executable, "-c", child_src],
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, \
        f"scale-out child failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


cold_child = run_child(with_cache=False)
warm_child = run_child(with_cache=True)
assert cold_child["compiles"] == 1, cold_child
assert cold_child["warm_hits"] == 0, cold_child
assert warm_child["compiles"] == 0, \
    f"fresh process COMPILED despite the persisted cache: {warm_child}"
assert warm_child["aot_loads"] == 1, warm_child
assert warm_child["warm_hits"] == 1, warm_child
# the band: the warm child's first request must sit well under the
# cold child's (same fixed costs, minus the compile; measured ~2x on
# this tiny model — the 25% margin absorbs 1-core CI scheduler
# jitter; the model is small on purpose, so the gate stays fast)
assert warm_child["first_request_ms"] < \
    cold_child["first_request_ms"] * 0.75, \
    (f"warm first request {warm_child['first_request_ms']:.1f}ms "
     f"not in band vs cold child "
     f"{cold_child['first_request_ms']:.1f}ms")

print(json.dumps({
    "fleet_gate": "ok",
    "swap_ms": swap_ms,
    "swap_futures_resolved": len(results),
    "swap_retraces": int(swap_retraces),
    "swap_steady_compiles": int(steady_compiles),
    "corruptions_counted": int(corruptions),
    "parent_cold_first_request_ms": round(cold_ms, 2),
    "cold_child_first_request_ms": cold_child["first_request_ms"],
    "warm_child_first_request_ms": warm_child["first_request_ms"],
    "warm_child_compiles": warm_child["compiles"],
}))
EOF
rm -rf "$FLEET_CACHE"
trap - EXIT

echo "== ci.sh: ALL GREEN =="
