"""sparkdl-lint (sparkdl_tpu.analysis) + runtime sanitizer tests.

Per rule: a positive fixture (deliberately broken code trips it), a
negative fixture (idiomatic clean code passes), and a suppressed
fixture (inline annotation downgrades without hiding). Plus the
meta-test: the shipped package itself must analyze to ZERO unsuppressed
findings — the gate tools/ci.sh step [10/11] enforces, pinned here so a
regressing module fails the suite before it fails CI.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import sparkdl_tpu
from sparkdl_tpu.analysis import (
    DEFAULT_ALLOWLIST,
    analyze_paths,
    analyze_source,
    format_findings,
)

PKG_DIR = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))


def _hits(source, rule, path="fixture.py"):
    return [f for f in analyze_source(source, path)
            if f.rule == rule and not f.suppressed]


def _suppressed(source, rule, path="fixture.py"):
    return [f for f in analyze_source(source, path)
            if f.rule == rule and f.suppressed]


# ---------------------------------------------------------------------------
# H1 — implicit host transfers


class TestH1Transfers:
    def test_device_get_trips(self):
        hits = _hits("import jax\n"
                     "def ship(res):\n"
                     "    return jax.device_get(res)\n", "H1")
        assert len(hits) == 1
        assert hits[0].line == 3
        assert "device_get" in hits[0].message
        assert hits[0].qualname == "ship"

    def test_block_until_ready_trips(self):
        hits = _hits("def wait(arr):\n"
                     "    arr.block_until_ready()\n", "H1")
        assert len(hits) == 1

    def test_np_asarray_on_jnp_call_trips(self):
        hits = _hits("import numpy as np\n"
                     "import jax.numpy as jnp\n"
                     "def f(x):\n"
                     "    return np.asarray(jnp.dot(x, x))\n", "H1")
        assert len(hits) == 1

    def test_np_asarray_on_host_value_clean(self):
        assert _hits("import numpy as np\n"
                     "def f(rows):\n"
                     "    return np.asarray(rows)\n", "H1") == []

    def test_trailing_suppression(self):
        src = ("import jax\n"
               "def drain(res):\n"
               "    return jax.device_get(res)"
               "  # sparkdl-lint: allow[H1] -- test drain\n")
        assert _hits(src, "H1") == []
        sup = _suppressed(src, "H1")
        assert len(sup) == 1
        assert "test drain" in sup[0].suppression

    def test_standalone_suppression_covers_next_line(self):
        src = ("import jax\n"
               "def drain(res):\n"
               "    # sparkdl-lint: allow[H1] -- standalone note\n"
               "    return jax.device_get(res)\n")
        assert _hits(src, "H1") == []
        assert len(_suppressed(src, "H1")) == 1

    def test_wrong_rule_suppression_does_not_apply(self):
        src = ("import jax\n"
               "def drain(res):\n"
               "    return jax.device_get(res)"
               "  # sparkdl-lint: allow[H2] -- wrong rule\n")
        assert len(_hits(src, "H1")) == 1

    def test_allowlist_scopes_by_qualname(self):
        src = ("import jax\n"
               "def timed_device_get(value):\n"
               "    return jax.device_get(value)\n"
               "def other(res):\n"
               "    return jax.device_get(res)\n")
        found = analyze_source(
            src, "sparkdl_tpu/obs/trace.py",
            allowlist=DEFAULT_ALLOWLIST)
        by_qual = {f.qualname: f.suppressed for f in found
                   if f.rule == "H1"}
        assert by_qual["timed_device_get"] is True
        assert by_qual["other"] is False


# ---------------------------------------------------------------------------
# H2 — jit/retrace hazards


class TestH2Retrace:
    def test_time_call_in_jitted_decorator(self):
        hits = _hits("import jax, time\n"
                     "@jax.jit\n"
                     "def step(x):\n"
                     "    t = time.perf_counter()\n"
                     "    return x * t\n", "H2")
        assert len(hits) == 1
        assert "trace" in hits[0].message.lower()

    def test_print_in_jit_call_form_named_fn(self):
        hits = _hits("import jax\n"
                     "def step(x):\n"
                     "    print(x)\n"
                     "    return x\n"
                     "jitted = jax.jit(step)\n", "H2")
        assert len(hits) == 1

    def test_np_random_in_partial_jit(self):
        hits = _hits("import jax\n"
                     "import numpy as np\n"
                     "from functools import partial\n"
                     "@partial(jax.jit, donate_argnums=(0,))\n"
                     "def step(x):\n"
                     "    return x + np.random.rand()\n", "H2")
        assert len(hits) == 1

    def test_jax_random_is_clean(self):
        assert _hits("import jax\n"
                     "@jax.jit\n"
                     "def step(key, x):\n"
                     "    return x + jax.random.normal(key, x.shape)\n",
                     "H2") == []

    def test_unjitted_time_is_clean(self):
        assert _hits("import time\n"
                     "def outer():\n"
                     "    return time.perf_counter()\n", "H2") == []

    def test_unhashable_static_argnums(self):
        hits = _hits("import jax\n"
                     "def f(x, n):\n"
                     "    return x\n"
                     "jitted = jax.jit(f, static_argnums=[1])\n", "H2")
        assert len(hits) == 1
        assert "static" in hits[0].message

    def test_tuple_static_argnums_clean(self):
        assert _hits("import jax\n"
                     "def f(x, n):\n"
                     "    return x\n"
                     "jitted = jax.jit(f, static_argnums=(1,))\n",
                     "H2") == []

    def test_suppressed(self):
        src = ("import jax, time\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    t = time.time()"
               "  # sparkdl-lint: allow[H2] -- trace-time stamp wanted\n"
               "    return x * t\n")
        assert _hits(src, "H2") == []
        assert len(_suppressed(src, "H2")) == 1


# ---------------------------------------------------------------------------
# H3 — concurrency discipline


class TestH3Concurrency:
    def test_lock_without_getstate_trips(self):
        hits = _hits("import threading\n"
                     "class Runner:\n"
                     "    def __init__(self):\n"
                     "        self._lock = threading.Lock()\n", "H3")
        assert len(hits) == 1
        assert "__getstate__" in hits[0].message

    def test_dataclass_field_lock_trips(self):
        hits = _hits("import threading\n"
                     "from dataclasses import dataclass, field\n"
                     "@dataclass\n"
                     "class Metrics:\n"
                     "    rows: int = 0\n"
                     "    _lock: threading.Lock = field(\n"
                     "        default_factory=threading.Lock)\n", "H3")
        assert len(hits) == 1

    def test_lock_with_getstate_clean(self):
        assert _hits("import threading\n"
                     "class Runner:\n"
                     "    def __init__(self):\n"
                     "        self._lock = threading.Lock()\n"
                     "    def __getstate__(self):\n"
                     "        s = self.__dict__.copy()\n"
                     "        del s['_lock']\n"
                     "        return s\n", "H3") == []

    def test_class_body_lock_exempt(self):
        # class attributes aren't pickled per-instance
        assert _hits("import threading\n"
                     "class Manifest:\n"
                     "    _lock = threading.Lock()\n", "H3") == []

    def test_guarded_write_outside_lock_trips(self):
        src = ("import threading\n"
               "class Metrics:\n"
               "    _lock_guards = ('rows',)\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.rows = 0\n"          # __init__ exempt
               "    def __getstate__(self):\n"
               "        return {}\n"
               "    def add(self, n):\n"
               "        self.rows += n\n")        # unlocked write
        hits = _hits(src, "H3")
        assert len(hits) == 1
        assert hits[0].line == 10
        assert "_lock_guards" in hits[0].message

    def test_guarded_write_inside_lock_clean(self):
        assert _hits("import threading\n"
                     "class Metrics:\n"
                     "    _lock_guards = ('rows',)\n"
                     "    def __init__(self):\n"
                     "        self._lock = threading.Lock()\n"
                     "        self.rows = 0\n"
                     "    def __getstate__(self):\n"
                     "        return {}\n"
                     "    def add(self, n):\n"
                     "        with self._lock:\n"
                     "            self.rows += n\n", "H3") == []

    def test_suppressed(self):
        src = ("import threading\n"
               "# sparkdl-lint: allow[H3] -- never ships to executors\n"
               "class Local:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n")
        assert _hits(src, "H3") == []
        assert len(_suppressed(src, "H3")) == 1

    def test_condition_holding_server_class_trips(self):
        """A Condition wraps (or owns) a mutex — a server-shaped class
        keeping one per instance has exactly the raw-Lock pickle
        problem (the serve layer's RequestQueue shape), and must not
        slip past H3 because it never says the word Lock."""
        src = ("import threading\n"
               "class RequestQueue:\n"
               "    def __init__(self):\n"
               "        self._cond = threading.Condition()\n"
               "    def offer(self, req):\n"
               "        with self._cond:\n"
               "            self._cond.notify()\n")
        hits = _hits(src, "H3")
        assert len(hits) == 1
        assert "_cond" in hits[0].message

    def test_condition_with_getstate_clean(self):
        """The serve queue's own discipline: drop-and-recreate hooks
        make a Condition-holding class clean."""
        assert _hits("import threading\n"
                     "class RequestQueue:\n"
                     "    def __init__(self):\n"
                     "        self._lock = threading.Lock()\n"
                     "        self._cond = threading.Condition("
                     "self._lock)\n"
                     "    def __getstate__(self):\n"
                     "        s = self.__dict__.copy()\n"
                     "        del s['_lock']\n"
                     "        del s['_cond']\n"
                     "        return s\n", "H3") == []


# ---------------------------------------------------------------------------
# H4 — quiesce hygiene


class TestH4Quiesce:
    def test_bare_except_trips(self):
        hits = _hits("def load():\n"
                     "    try:\n"
                     "        return open('x')\n"
                     "    except:\n"
                     "        return None\n", "H4")
        assert len(hits) == 1
        assert "bare" in hits[0].message

    def test_swallow_in_finally_trips(self):
        hits = _hits("def run(pending):\n"
                     "    try:\n"
                     "        yield 1\n"
                     "    finally:\n"
                     "        for fut in pending:\n"
                     "            try:\n"
                     "                fut.result()\n"
                     "            except Exception:\n"
                     "                pass\n", "H4")
        assert len(hits) == 1
        assert "swallow" in hits[0].message

    def test_swallow_in_close_trips(self):
        hits = _hits("class Src:\n"
                     "    def close(self):\n"
                     "        try:\n"
                     "            self.f.close()\n"
                     "        except OSError:\n"
                     "            pass\n", "H4")
        assert len(hits) == 1

    def test_logged_handler_clean(self):
        assert _hits("import logging\n"
                     "def close(f):\n"
                     "    try:\n"
                     "        f.close()\n"
                     "    except OSError as e:\n"
                     "        logging.debug('close: %s', e)\n",
                     "H4") == []

    def test_swallow_outside_cleanup_clean(self):
        # a probe in a hot-path helper may legitimately swallow
        assert _hits("def probe(x):\n"
                     "    try:\n"
                     "        return x.copy_to_host_async()\n"
                     "    except NotImplementedError:\n"
                     "        pass\n", "H4") == []

    def test_suppressed(self):
        src = ("def close(f):\n"
               "    try:\n"
               "        f.close()\n"
               "    # sparkdl-lint: allow[H4] -- double-close is fine\n"
               "    except OSError:\n"
               "        pass\n")
        assert _hits(src, "H4") == []
        assert len(_suppressed(src, "H4")) == 1


# ---------------------------------------------------------------------------
# H5 — clock discipline in obs/serve


class TestH5Clock:
    """Span/latency math in sparkdl_tpu/obs/ and sparkdl_tpu/serve/
    must share the tracer's perf_counter clock — wall-clock reads there
    are flagged; the same code anywhere else is not (path-scoped)."""

    def test_time_time_in_obs_trips(self):
        hits = _hits("import time\n"
                     "def span_end():\n"
                     "    return time.time()\n", "H5",
                     path="sparkdl_tpu/obs/fixture.py")
        assert len(hits) == 1
        assert "perf_counter" in hits[0].message
        assert hits[0].qualname == "span_end"

    def test_datetime_now_in_serve_trips(self):
        hits = _hits("from datetime import datetime\n"
                     "def deadline():\n"
                     "    return datetime.now()\n", "H5",
                     path="sparkdl_tpu/serve/fixture.py")
        assert len(hits) == 1

    def test_datetime_module_form_trips(self):
        hits = _hits("import datetime\n"
                     "def stamp():\n"
                     "    return datetime.datetime.utcnow()\n", "H5",
                     path="sparkdl_tpu/obs/fixture.py")
        assert len(hits) == 1

    def test_perf_counter_is_clean(self):
        assert _hits("import time\n"
                     "def now():\n"
                     "    return time.perf_counter()\n", "H5",
                     path="sparkdl_tpu/obs/fixture.py") == []

    def test_wall_clock_outside_obs_serve_is_clean(self):
        src = ("import time\n"
               "def bench_stamp():\n"
               "    return time.time()\n")
        assert _hits(src, "H5", path="fixture.py") == []
        assert _hits(src, "H5",
                     path="sparkdl_tpu/runtime/fixture.py") == []

    def test_suppressed(self):
        src = ("import time\n"
               "def stamp():\n"
               "    return time.time()"
               "  # sparkdl-lint: allow[H5] -- artifact stamp\n")
        path = "sparkdl_tpu/obs/fixture.py"
        assert _hits(src, "H5", path=path) == []
        sup = _suppressed(src, "H5", path=path)
        assert len(sup) == 1
        assert "artifact stamp" in sup[0].suppression

    def test_meta_flight_bundle_stamp_is_suppressed_not_invisible(self):
        """The one legitimate wall-clock read in obs/ — the flight
        bundle's written_unix stamp — must APPEAR as a suppressed H5
        finding (the allowlist-not-skipped discipline, H1 precedent)."""
        found = analyze_paths([os.path.join(PKG_DIR, "obs")])
        h5 = [f for f in found if f.rule == "H5"]
        assert h5, "expected the flight.py bundle stamp to be flagged"
        assert all(f.suppressed for f in h5), format_findings(
            [f for f in h5 if not f.suppressed])
        assert any("flight.py" in f.path for f in h5)


# ---------------------------------------------------------------------------
# H6 — metric-name cardinality (request ids must never become keys)


class TestH6Cardinality:
    """A registry metric name interpolating a request id grows one
    eternal registry entry + Prometheus series per request — flagged
    anywhere; bounded dynamic names (configured knobs) and constant
    names are not."""

    def test_fstring_request_id_name_trips(self):
        hits = _hits("def publish(reg, request_id):\n"
                     "    reg.counter(\n"
                     "        f'serve.req.{request_id}.rows').add()\n",
                     "H6")
        assert len(hits) == 1
        assert "cardinality" in hits[0].message
        assert hits[0].qualname == "publish"

    def test_concat_and_attribute_forms_trip(self):
        src = ("def publish(reg, req):\n"
               "    reg.gauge('serve.' + req.rid).set(1)\n"
               "    reg.reservoir('lat.' + req.request_id)\n")
        hits = _hits(src, "H6")
        assert len(hits) == 2

    def test_format_call_trips(self):
        hits = _hits("def publish(reg, rid):\n"
                     "    reg.gauge('serve.{}.depth'.format(rid))\n",
                     "H6")
        assert len(hits) == 1

    def test_keyword_name_form_trips(self):
        # the name= kwarg spelling is just as legal a call form — it
        # must not be a loophole
        hits = _hits("def publish(reg, request_id):\n"
                     "    reg.counter(\n"
                     "        name=f'req.{request_id}.rows').add()\n",
                     "H6")
        assert len(hits) == 1

    def test_constant_and_bounded_dynamic_names_are_clean(self):
        # constant names, and dynamic names over bounded key sets (the
        # autotune knob-gauge idiom) must NOT trip — the rule is about
        # request-shaped identifiers, not dynamism per se
        src = ("def publish(reg, target, knob):\n"
               "    reg.counter('obs.request_log.dropped').add()\n"
               "    reg.gauge(f'autotune.knob.{target}.{knob}')\n")
        assert _hits(src, "H6") == []

    def test_request_id_outside_metric_name_is_clean(self):
        # ids in exemplars / span args / log records are exactly where
        # they belong — only metric NAMES are the hazard
        src = ("def observe(res, rid, lat):\n"
               "    res.observe(lat, exemplar={'request_id': rid})\n")
        assert _hits(src, "H6") == []

    def test_suppressed_with_justification(self):
        """The worked inline-suppression fixture: a variable that only
        SOUNDS request-shaped but draws from a bounded set suppresses
        with the reason the key set is bounded."""
        src = ("def count_findings(reg, rid):\n"
               "    # rid here is a LINT RULE id (H1..H6), six values\n"
               "    reg.counter(f'lint.{rid}.findings').add()"
               "  # sparkdl-lint: allow[H6] -- rid is a lint rule id "
               "(H1..H6, a bounded set), not a request id\n")
        assert _hits(src, "H6") == []
        sup = _suppressed(src, "H6")
        assert len(sup) == 1
        assert "bounded set" in sup[0].suppression

    def test_meta_obs_and_serve_are_h6_clean(self):
        """The layers that actually handle request ids ship H6-clean:
        ids flow through the RequestLog/exemplars/span args, never
        into registry keys."""
        found = analyze_paths([os.path.join(PKG_DIR, "obs"),
                               os.path.join(PKG_DIR, "serve")])
        h6 = [f for f in found if f.rule == "H6" and not f.suppressed]
        assert h6 == [], format_findings(h6)


# ---------------------------------------------------------------------------
# walker / CLI / formatter


class TestHarness:
    def test_syntax_error_reports_parse_finding(self):
        found = analyze_source("def broken(:\n", "bad.py")
        assert [f.rule for f in found] == ["PARSE"]
        assert not found[0].suppressed

    def test_format_text_has_path_line_col(self):
        found = analyze_source(
            "import jax\nx = jax.device_get(1)\n", "mod.py")
        text = format_findings(found)
        assert text.startswith("mod.py:2:")

    def test_format_json(self):
        found = analyze_source(
            "import jax\nx = jax.device_get(1)\n", "mod.py")
        d = json.loads(format_findings(found, fmt="json"))
        assert d["unsuppressed"] == 1
        assert d["findings"][0]["rule"] == "H1"

    def test_cli_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\nx = jax.device_get(1)\n")
        ok = tmp_path / "ok.py"
        ok.write_text("x = 1\n")
        env = {**os.environ,
               "PYTHONPATH": os.path.dirname(PKG_DIR)}
        r = subprocess.run(
            [sys.executable, "-m", "sparkdl_tpu.analysis", str(bad)],
            capture_output=True, text=True, env=env)
        assert r.returncode == 1
        assert "H1" in r.stdout
        r = subprocess.run(
            [sys.executable, "-m", "sparkdl_tpu.analysis", str(ok)],
            capture_output=True, text=True, env=env)
        assert r.returncode == 0

    def test_meta_package_is_clean(self):
        """THE gate: the shipped package analyzes to zero unsuppressed
        findings — every legitimate drain/swallow carries an inline
        justification or a scoped allowlist entry."""
        found = analyze_paths([PKG_DIR])
        unsuppressed = [f for f in found if not f.suppressed]
        assert unsuppressed == [], format_findings(unsuppressed)
        # and the suppressions that exist all carry a justification
        for f in found:
            if f.suppressed:
                assert f.suppression, f.render()

    def test_meta_serve_package_is_clean(self):
        """The serve layer is the newest lock-heavy subsystem — pin it
        by name (zero unsuppressed H1–H4) so a refactor that breaks its
        lock-pickle/quiesce discipline names the right package instead
        of hiding in the whole-tree gate above."""
        found = analyze_paths([os.path.join(PKG_DIR, "serve")])
        unsuppressed = [f for f in found if not f.suppressed]
        assert unsuppressed == [], format_findings(unsuppressed)

    def test_meta_autotune_package_is_clean(self):
        """The autotune layer writes to knobs other threads' hot loops
        read and keeps its own lock-guarded counters — pin it by name
        (zero unsuppressed H1–H5) so a controller refactor that breaks
        the lock/clock discipline names the right package instead of
        hiding in the whole-tree gate above."""
        found = analyze_paths([os.path.join(PKG_DIR, "autotune")])
        unsuppressed = [f for f in found if not f.suppressed]
        assert unsuppressed == [], format_findings(unsuppressed)

    def test_meta_known_drains_are_suppressed_not_invisible(self):
        """The drain path is allowlisted, not skipped: the single
        blessed device_get — obs/trace.py::timed_device_get, where
        SlabSink.write's drain moved so it could be spanned — must
        APPEAR as a suppressed finding."""
        found = analyze_paths([PKG_DIR])
        quals = {f.qualname for f in found
                 if f.rule == "H1" and f.suppressed}
        assert "timed_device_get" in quals


# ---------------------------------------------------------------------------
# the real findings the first analyzer run surfaced — pinned fixed


class TestFirstRunFindingsFixed:
    """H3 hits from the analyzer's first pass over the repo: three
    lock-holding classes with no pickle hooks. Spark ships stage
    closures with cloudpickle; each must survive the wire."""

    def test_sharded_runner_ships(self):
        import cloudpickle as cp
        from sparkdl_tpu.graph.function import ModelFunction
        from sparkdl_tpu.parallel.inference import ShardedBatchRunner
        mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                      input_shape=(3,))
        r = cp.loads(cp.dumps(ShardedBatchRunner(mf, batch_size=1)))
        n = r.preferred_chunk  # re-derived from local devices
        x = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)

    def test_local_engine_ships(self):
        import cloudpickle as cp
        from sparkdl_tpu.data.engine import LocalEngine
        e = cp.loads(cp.dumps(LocalEngine(num_workers=2)))
        assert list(e.execute([], [])) == []
        e.shutdown()

    def test_stage_metrics_ships(self):
        import cloudpickle as cp
        from sparkdl_tpu.utils.profiling import StageMetrics
        m = StageMetrics()
        m.add("decode", 0.5, 10)
        m2 = cp.loads(cp.dumps(m))
        m2.add("decode", 0.5, 10)
        assert m2.as_dict()["decode"]["rows"] == 20


# ---------------------------------------------------------------------------
# runtime sanitizer


class TestSanitizer:
    def _model_and_input(self):
        from sparkdl_tpu.graph.function import ModelFunction
        mf = ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                      input_shape=(3,))
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        return mf, x

    def test_aligned_run_sanitized_matches_unsanitized(self, monkeypatch):
        from sparkdl_tpu.runtime.runner import BatchRunner, RunnerMetrics
        mf, x = self._model_and_input()
        monkeypatch.delenv("SPARKDL_TPU_SANITIZE", raising=False)
        base = BatchRunner(mf, batch_size=4).run({"input": x})["output"]
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        m = RunnerMetrics()
        out = BatchRunner(mf, batch_size=4, metrics=m).run(
            {"input": x})["output"]
        np.testing.assert_array_equal(base, out)
        # the aligned zero-copy contract holds under the guard
        assert m.bytes_staged == 0
        assert m.bytes_copied == 0

    @pytest.mark.parametrize("max_inflight", [0, 2])
    def test_every_depth_completes_sanitized(self, monkeypatch,
                                             max_inflight):
        from sparkdl_tpu.runtime.runner import BatchRunner
        mf, x = self._model_and_input()
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        out = BatchRunner(mf, batch_size=4,
                          max_inflight=max_inflight).run(
            {"input": x})["output"]
        np.testing.assert_allclose(out, x * 2)

    def test_tail_run_sanitized(self, monkeypatch):
        from sparkdl_tpu.runtime.runner import BatchRunner
        mf, x = self._model_and_input()
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        out = BatchRunner(mf, batch_size=4).run(
            {"input": x[:7]})["output"]
        np.testing.assert_allclose(out, x[:7] * 2)

    def test_sharded_runner_sanitized(self, monkeypatch):
        import jax
        if len(jax.local_devices()) < 2:
            pytest.skip("needs >1 device (ci.sh forces 8 virtual)")
        from sparkdl_tpu.parallel.inference import ShardedBatchRunner
        mf, x = self._model_and_input()
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        runner = ShardedBatchRunner(mf, batch_size=1)
        n = runner.preferred_chunk
        xs = np.arange(n * 3, dtype=np.float32).reshape(n, 3)
        out = runner.run({"input": xs})["output"]
        np.testing.assert_allclose(out, xs * 2)

    def test_guard_arms_or_degrades_once(self, monkeypatch):
        from sparkdl_tpu.runtime import sanitize
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        before = sanitize.armed_run_count()
        with sanitize.ship_guard() as armed:
            assert armed is True
        # the armed counter is what a reporter of sanitized runs reads —
        # env-on alone must not count (degraded guard ≠ enforced)
        assert sanitize.armed_run_count() == before + 1

    def test_guard_off_by_default(self, monkeypatch):
        from sparkdl_tpu.runtime import sanitize
        monkeypatch.delenv("SPARKDL_TPU_SANITIZE", raising=False)
        with sanitize.ship_guard() as armed:
            assert armed is False

    def test_degrades_with_single_warning_when_guard_cannot_arm(
            self, monkeypatch, caplog):
        import jax
        from sparkdl_tpu.runtime import sanitize

        class _CannotArm:
            def __enter__(self):
                raise NotImplementedError("no guard on this backend")

        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        monkeypatch.setattr(sanitize, "_warned_no_guard", False)
        monkeypatch.setattr(jax, "transfer_guard_device_to_host",
                            lambda level: _CannotArm())
        with caplog.at_level("WARNING",
                             logger="sparkdl_tpu.runtime.sanitize"):
            with sanitize.ship_guard() as armed:
                assert armed is False
            with sanitize.ship_guard() as armed:
                assert armed is False
        warnings = [r for r in caplog.records
                    if "unguarded" in r.getMessage()]
        assert len(warnings) == 1  # probe-and-degrade warns ONCE

    def test_guard_blocks_implicit_transfer_when_backend_supports(
            self, monkeypatch):
        """On CPU, arrays are host-resident and a d2h guard has nothing
        to catch — but the guard plumbing must still reject implicit
        transfers wherever jax reports them. Exercise the context
        directly: entering must not swallow real errors raised inside."""
        from sparkdl_tpu.runtime import sanitize
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        with pytest.raises(RuntimeError, match="boom"):
            with sanitize.ship_guard():
                raise RuntimeError("boom")


# ---------------------------------------------------------------------------
# H13 — unbounded retry loops (serve/runtime/data/resilience paths)


class TestH13RetryLoops:
    PATH = "sparkdl_tpu/serve/fixture.py"

    def test_bare_while_true_swallow_flagged(self):
        src = ("def pump(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        except Exception:\n"
               "            pass\n")
        found = _hits(src, "H13", self.PATH)
        assert len(found) == 1
        assert "bounded and backed-off" in found[0].message

    def test_while_one_log_and_continue_flagged(self):
        src = ("import logging\n"
               "def pump(q):\n"
               "    while 1:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        except Exception as e:\n"
               "            logging.warning('retrying: %s', e)\n"
               "            continue\n")
        assert len(_hits(src, "H13", self.PATH)) == 1

    def test_handler_that_reraises_clean(self):
        # the RetryPolicy.call shape: the handler re-raises when the
        # grant is refused — bounded by construction
        src = ("def call(fn, policy):\n"
               "    attempt = 0\n"
               "    while True:\n"
               "        try:\n"
               "            return fn()\n"
               "        except Exception as exc:\n"
               "            attempt += 1\n"
               "            delay = policy.grant(attempt, exc)\n"
               "            if delay is None:\n"
               "                raise\n"
               "            policy.sleep(delay)\n")
        assert _hits(src, "H13", self.PATH) == []

    def test_handler_that_breaks_clean(self):
        src = ("def pump(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        except Exception:\n"
               "            break\n")
        assert _hits(src, "H13", self.PATH) == []

    def test_try_inside_nested_for_still_flagged(self):
        # a per-iteration-bounded inner loop does not bound the OUTER
        # while True: the swallow re-enters it forever
        src = ("def pump(q):\n"
               "    while True:\n"
               "        for item in q.batch():\n"
               "            try:\n"
               "                q.dispatch(item)\n"
               "            except Exception:\n"
               "                pass\n")
        assert len(_hits(src, "H13", self.PATH)) == 1

    def test_break_of_inner_loop_is_not_an_escape(self):
        # the break exits the handler's own for, not the while True —
        # the outer loop still spins forever on sustained failure
        src = ("def pump(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        except Exception:\n"
               "            for h in q.hooks:\n"
               "                break\n")
        assert len(_hits(src, "H13", self.PATH)) == 1

    def test_nested_unbounded_while_flagged_once_at_its_own_loop(self):
        src = ("def pump(q):\n"
               "    while True:\n"
               "        while True:\n"
               "            try:\n"
               "                q.dispatch()\n"
               "            except Exception:\n"
               "                pass\n"
               "        return\n")
        assert len(_hits(src, "H13", self.PATH)) == 1

    def test_bounded_for_loop_not_flagged(self):
        src = ("def pump(q):\n"
               "    for attempt in range(3):\n"
               "        try:\n"
               "            return q.dispatch()\n"
               "        except Exception:\n"
               "            pass\n")
        assert _hits(src, "H13", self.PATH) == []

    def test_nested_def_handlers_not_attributed_to_outer_loop(self):
        # a callback defined inside the loop owns its own handlers
        src = ("def pump(q):\n"
               "    while True:\n"
               "        def cb():\n"
               "            try:\n"
               "                q.poke()\n"
               "            except Exception:\n"
               "                pass\n"
               "        if not q.step(cb):\n"
               "            return\n")
        assert _hits(src, "H13", self.PATH) == []

    def test_out_of_scope_path_ignored(self):
        src = ("def pump(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        except Exception:\n"
               "            pass\n")
        assert _hits(src, "H13", "sparkdl_tpu/models/fixture.py") == []

    def test_suppressed_with_justification(self):
        src = ("def pump(q):\n"
               "    while True:\n"
               "        try:\n"
               "            q.dispatch()\n"
               "        # sparkdl-lint: allow[H13] -- paced by q's blocking wait; exits via q.closed\n"
               "        except Exception:\n"
               "            q.note_failure()\n")
        assert _hits(src, "H13", self.PATH) == []
        assert len(_suppressed(src, "H13", self.PATH)) == 1

    def test_serve_loop_suppression_is_visible_not_invisible(self):
        """The package's one real H13 — the dispatcher's serve loop —
        must APPEAR as a suppressed finding with its justification."""
        found = analyze_paths(
            [os.path.join(PKG_DIR, "serve")], cache_path=None)
        h13 = [f for f in found if f.rule == "H13"]
        assert any(f.suppressed and "RetryPolicy" in f.suppression
                   for f in h13), [f.render() for f in h13]
        assert not any(not f.suppressed for f in h13)
