"""Effect-system tests: the H10 jit-purity closure (cross-module
witness chains + mutable-capture analysis), H11 resource-lifecycle
tracking (escape-analysis negatives pinned silent), H12 exception-flow
accounting, SARIF 2.1.0 output, ``--changed-only``, and the
facts-schema cache invalidation contract.

Fixture style mirrors tests/test_callgraph.py: deliberately impure /
leaky multi-module trees under tmp_path trip the rules; the idiomatic
clean forms don't; inline suppressions downgrade without hiding. The
acceptance bars from ISSUE 10: a jitted function transitively calling
a registry counter through two modules is caught WITH the full
witness chain; a mutable-instance-attr capture is caught; an unclosed
ModelServer is caught while every escape-analysis negative stays
silent; a swallowing serve handler is caught while the
counter-recording form is accepted; the real package + tools +
examples are lint-clean under all nineteen rules (H13 rode in with
ISSUE 11's resilience layer; H14-H16 with ISSUE 12's device-dataflow
layer; H17-H19 with ISSUE 17's static race detector).
"""

import json
import os
import subprocess
import sys
import time

import pytest

import sparkdl_tpu
from sparkdl_tpu.analysis import analyze_paths, build_graph, to_sarif
from sparkdl_tpu.analysis import cache as cache_mod
from sparkdl_tpu.analysis.effects import may_effect
from sparkdl_tpu.analysis.walker import ALL_RULES, analyze_source

PKG_DIR = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))
REPO_ROOT = os.path.dirname(PKG_DIR)


def _tree(tmp_path, files: dict) -> str:
    for name, src in files.items():
        (tmp_path / name).write_text(src)
    return str(tmp_path)


def _unsup(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def _sup(findings, rule):
    return [f for f in findings if f.rule == rule and f.suppressed]


def _run_cli(*args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "sparkdl_tpu.analysis", *args],
        capture_output=True, text=True, env=env,
        cwd=cwd or REPO_ROOT)


# ---------------------------------------------------------------------------
# H10 — effectful call reachable from jit


class TestH10JitPurity:
    def test_registry_counter_through_two_modules_with_witness(
            self, tmp_path):
        """THE acceptance fixture: a jitted step transitively calls a
        registry counter through two modules — the finding prints the
        full module-by-module witness chain."""
        root = _tree(tmp_path, {
            "metrics_mod.py": (
                "def bump(reg):\n"
                "    reg.counter('train.steps').add()\n"),
            "helper_mod.py": (
                "from metrics_mod import bump\n"
                "def helper(x, reg):\n"
                "    bump(reg)\n"
                "    return x\n"),
            "train_mod.py": (
                "import jax\n"
                "from helper_mod import helper\n"
                "@jax.jit\n"
                "def step(x, reg):\n"
                "    return helper(x, reg)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1, [f.render() for f in found]
        msg = hits[0].message
        assert "train_mod:step" in msg
        assert "helper_mod:helper" in msg
        assert "metrics_mod:bump" in msg
        assert "registry" in msg
        assert hits[0].path.endswith("train_mod.py")

    def test_mutable_instance_attr_capture(self, tmp_path):
        """THE second acceptance fixture: a jitted method capturing a
        mutable instance attr (the stale-value/retrace hazard)."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "class Trainer:\n"
            "    def __init__(self):\n"
            "        self.history = []\n"
            "    @jax.jit\n"
            "    def traced(self, x):\n"
            "        return x + len(self.history)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1, [f.render() for f in found]
        assert "self.history" in hits[0].message
        assert "mutable instance attribute" in hits[0].message

    def test_mutable_closure_capture(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def outer():\n"
            "    accum = []\n"
            "    @jax.jit\n"
            "    def inner(x):\n"
            "        return x + len(accum)\n"
            "    return inner\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1, [f.render() for f in found]
        assert "`accum`" in hits[0].message
        assert "closure" in hits[0].message

    def test_param_shadowing_is_not_a_capture(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def outer():\n"
            "    accum = []\n"
            "    @jax.jit\n"
            "    def inner(accum):\n"      # param shadows the list
            "        return len(accum)\n"
            "    return inner\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []

    def test_nested_def_local_does_not_shadow_a_capture(
            self, tmp_path):
        """A NESTED helper's local `accum = ...` must not shadow the
        jitted function's genuine closure capture of the enclosing
        `accum` (scope-pruned locals collection)."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def outer():\n"
            "    accum = []\n"
            "    @jax.jit\n"
            "    def step(x):\n"
            "        y = x + len(accum)\n"
            "        def helper():\n"
            "            accum = 1\n"
            "            return accum\n"
            "        return y\n"
            "    return step\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1, [f.render() for f in found]
        assert "`accum`" in hits[0].message

    def test_pure_jit_fn_is_clean(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def pure_helper(x):\n"
            "    return x * 2\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return pure_helper(x) + jnp.sum(x)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []

    def test_effect_not_reachable_from_jit_is_clean(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def effectful(reg):\n"
            "    reg.counter('x.y').add()\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []

    def test_direct_registry_write_in_jit_body(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "@jax.jit\n"
            "def step(x, reg):\n"
            "    reg.counter('steps').add()\n"
            "    return x\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1
        assert "TRACE time" in hits[0].message

    def test_direct_clock_is_h2_territory_not_h10(self, tmp_path):
        """A literal time.time() inside the jit body is H2's lexical
        beat — H10 flagging the same line would demand two
        suppressions for one decision."""
        root = _tree(tmp_path, {"m.py": (
            "import jax, time\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    t = time.time()\n"
            "    return x + t\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []
        found2 = analyze_paths([root], rules=["H2"], cache_path=None)
        assert len(_unsup(found2, "H2")) == 1

    def test_transitive_clock_IS_h10(self, tmp_path):
        """...but the same clock reached through a call chain is
        exactly what H2 cannot see and H10 exists for."""
        root = _tree(tmp_path, {"m.py": (
            "import jax, time\n"
            "def stamp():\n"
            "    return time.time()\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return x + stamp()\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        hits = _unsup(found, "H10")
        assert len(hits) == 1
        assert "time.time" in hits[0].message
        found2 = analyze_paths([root], rules=["H2"], cache_path=None)
        assert _unsup(found2, "H2") == []

    def test_unique_method_edges_are_not_followed(self, tmp_path):
        """A jit body calling obj.update() must NOT bind to the one
        analyzed class defining `update` — optimizer objects live
        outside the analyzed set, and a guessed edge manufactures
        false impurity."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "class Registryish:\n"
            "    def update(self, reg):\n"
            "        reg.counter('x.y').add()\n"
            "@jax.jit\n"
            "def step(x, opt, state):\n"
            "    return opt.update(state)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []

    def test_partial_jit_outer_call_form_marks_named_def(
            self, tmp_path):
        """`partial(jax.jit, ...)(step)`: the traced fn rides the
        OUTER call's args — it must still be marked a jit root."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "from functools import partial\n"
            "def make():\n"
            "    def step(x, reg):\n"
            "        reg.counter('steps').add()\n"
            "        return x\n"
            "    return partial(jax.jit, donate_argnums=(0,))(step)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert len(_unsup(found, "H10")) == 1, \
            [f.render() for f in found]

    def test_jit_root_inside_match_case_is_seen(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def fit(mode):\n"
            "    match mode:\n"
            "        case 'train':\n"
            "            @jax.jit\n"
            "            def step(x, reg):\n"
            "                reg.counter('steps').add()\n"
            "                return x\n"
            "            return step\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert len(_unsup(found, "H10")) == 1, \
            [f.render() for f in found]

    def test_jit_call_form_marks_named_def(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def make():\n"
            "    def step(x, reg):\n"
            "        reg.gauge('depth').set(x)\n"
            "        return x\n"
            "    return jax.jit(step)\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert len(_unsup(found, "H10")) == 1

    def test_jitted_step_inside_epoch_loop_is_seen(self, tmp_path):
        """The streaming-estimator idiom: the jitted def sits inside
        a for/if block, not at the function body's top level — the
        def walk must still find it (the PR-8 walk missed these)."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def fit(first):\n"
            "    if first:\n"
            "        @jax.jit\n"
            "        def step(x, reg):\n"
            "            reg.counter('steps').add()\n"
            "            return x\n"
            "        return step\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert len(_unsup(found, "H10")) == 1

    def test_suppressed_with_reason(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def log_shape(x):\n"
            "    print(x.shape)\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    log_shape(x)  # sparkdl-lint: allow[H10] -- "
            "trace-time shape echo is the point (debug build only)\n"
            "    return x\n")})
        found = analyze_paths([root], rules=["H10"], cache_path=None)
        assert _unsup(found, "H10") == []
        sup = _sup(found, "H10")
        assert len(sup) == 1
        assert "shape echo" in sup[0].suppression

    def test_may_effect_closure_dedups_and_chains(self, tmp_path):
        root = _tree(tmp_path, {
            "a.py": ("from b import mid\n"
                     "def top(reg):\n"
                     "    mid(reg)\n"
                     "    mid(reg)\n"),
            "b.py": ("def mid(reg):\n"
                     "    reg.counter('k.v').add()\n")})
        g = build_graph([os.path.join(root, "a.py"),
                         os.path.join(root, "b.py")])
        key = next(k for k, f in g.functions.items()
                   if f.qualname == "top")
        eff = may_effect(g, key)
        regs = [(k, chain) for k, chain in eff.items()
                if k[0] == "registry"]
        assert len(regs) == 1
        (_, chain) = regs[0]
        assert chain[0].endswith("a:top") and chain[-1].endswith("b:mid")


# ---------------------------------------------------------------------------
# H11 — resource lifecycle


_SRV = ("class ModelServer:\n"
        "    def submit(self, x):\n"
        "        return x\n"
        "    def close(self):\n"
        "        pass\n")


class TestH11ResourceLifecycle:
    def test_unclosed_modelserver_is_caught(self, tmp_path):
        """THE acceptance fixture: a ModelServer constructed, used,
        and abandoned — cross-module ctor resolution included."""
        root = _tree(tmp_path, {
            "srv.py": _SRV,
            "use.py": ("from srv import ModelServer\n"
                       "def serve_once(x):\n"
                       "    s = ModelServer()\n"
                       "    return s.submit(x)\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        hits = _unsup(found, "H11")
        assert len(hits) == 1, [f.render() for f in found]
        assert "ModelServer" in hits[0].message
        assert "close()" in hits[0].message
        assert hits[0].path.endswith("use.py")

    @pytest.mark.parametrize("body", [
        # returned
        "    s = ModelServer()\n    return s\n",
        # stored on self/attr
        "    s = ModelServer()\n    holder.srv = s\n",
        # stored in a container
        "    s = ModelServer()\n    holder['k'] = s\n",
        # weakly registered / passed to a function
        "    s = ModelServer()\n    reg.register(s)\n",
        # terminated
        "    s = ModelServer()\n    s.close()\n",
        # terminated in a finally
        "    s = ModelServer()\n    try:\n        s.submit(1)\n"
        "    finally:\n        s.close()\n",
        # used as a context manager
        "    s = ModelServer()\n    with s:\n        pass\n",
    ], ids=["returned", "stored-attr", "stored-subscript",
            "registered", "closed", "finally-closed", "with"])
    def test_escape_analysis_negatives_stay_silent(self, tmp_path,
                                                   body):
        root = _tree(tmp_path, {
            "srv.py": _SRV,
            "use.py": ("from srv import ModelServer\n"
                       "def f(holder, reg):\n" + body)})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == [], \
            [f.render() for f in _unsup(found, "H11")]

    def test_global_storage_escapes(self, tmp_path):
        root = _tree(tmp_path, {
            "srv.py": _SRV,
            "use.py": ("from srv import ModelServer\n"
                       "_default = None\n"
                       "def default_server():\n"
                       "    global _default\n"
                       "    _default = ModelServer()\n"
                       "    return _default\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []

    def test_open_handle_leak_and_with_form(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "def leaky(p):\n"
            "    f = open(p)\n"
            "    return f.read()\n"       # escape? no: f.read() is
            "def fine(p):\n"               # receiver use, not escape
            "    with open(p) as f:\n"
            "        return f.read()\n"
            "def closed(p):\n"
            "    f = open(p)\n"
            "    data = f.read()\n"
            "    f.close()\n"
            "    return data\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        hits = _unsup(found, "H11")
        assert len(hits) == 1, [f.render() for f in hits]
        assert hits[0].qualname == "leaky"

    def test_arm_without_disarm_is_caught(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "from sparkdl_tpu.obs.watchdog import watchdog\n"
            "def measure():\n"
            "    wd = watchdog()\n"
            "    wd.arm(threshold_s=0.5)\n"
            "    run()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        hits = _unsup(found, "H11")
        assert len(hits) == 1
        assert "disarm" in hits[0].message

    def test_arm_with_disarm_is_clean(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "from sparkdl_tpu.obs.watchdog import watchdog\n"
            "def measure():\n"
            "    wd = watchdog()\n"
            "    wd.arm(threshold_s=0.5)\n"
            "    try:\n"
            "        run()\n"
            "    finally:\n"
            "        wd.disarm()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []

    def test_direct_singleton_arm_form(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "from sparkdl_tpu.obs.trace import tracer\n"
            "def measure():\n"
            "    tracer().arm()\n"
            "    run()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert len(_unsup(found, "H11")) == 1
        (tmp_path / "ok").mkdir()
        root2 = _tree(tmp_path / "ok", {"m.py": (
            "from sparkdl_tpu.obs.trace import tracer\n"
            "def measure():\n"
            "    tracer().arm()\n"
            "    run()\n"
            "    tracer().disarm()\n")})
        found2 = analyze_paths([root2], rules=["H11"], cache_path=None)
        assert _unsup(found2, "H11") == []

    def test_arm_in_nested_def_belongs_to_the_nested_scope(
            self, tmp_path):
        """An arm inside a nested callback is the CALLBACK's
        lifecycle, not the enclosing function's — exactly one finding,
        anchored in the nested def (the scope-pruned walk)."""
        root = _tree(tmp_path, {"m.py": (
            "from sparkdl_tpu.obs.watchdog import watchdog\n"
            "def setup(register):\n"
            "    def cb():\n"
            "        watchdog().arm(threshold_s=1.0)\n"
            "        run()\n"
            "    register(cb)\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        hits = _unsup(found, "H11")
        assert len(hits) == 1, [f.render() for f in hits]
        assert hits[0].qualname == "setup.cb"

    def test_terminator_inside_nested_def_does_not_silence(
            self, tmp_path):
        """A close() sitting inside a maybe-never-called nested def
        must NOT count as the outer scope's termination. (The ctor
        form escapes via nested-def capture instead; the arm form has
        no capturable name, so this pins the real hole.)"""
        root = _tree(tmp_path, {"m.py": (
            "from sparkdl_tpu.obs.watchdog import watchdog\n"
            "def measure(register):\n"
            "    watchdog().arm(threshold_s=1.0)\n"
            "    def later():\n"
            "        watchdog().disarm()\n"
            "    register(later)\n"
            "    run()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        hits = _unsup(found, "H11")
        assert len(hits) == 1, [f.render() for f in hits]
        assert hits[0].qualname == "measure"

    def test_unresolvable_ctor_is_silent(self, tmp_path):
        """A class the analyzer cannot see (third-party) gives no
        verdict — a guessed lifecycle would be a false positive."""
        root = _tree(tmp_path, {"m.py": (
            "from somewhere import Mystery\n"
            "def f():\n"
            "    m = Mystery()\n"
            "    m.use()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []

    def test_ambiguous_class_name_is_silent(self, tmp_path):
        """Two analyzed modules define `Server` (one with close, one
        without): the unique-class fallback must refuse, like the
        unique-method heuristic does."""
        root = _tree(tmp_path, {
            "a.py": "class Server:\n    def close(self):\n        pass\n",
            "b.py": "class Server:\n    def ping(self):\n        pass\n",
            "use.py": ("def f(make):\n"
                       "    s = Server()\n"
                       "    s.ping()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []

    def test_non_resource_class_is_silent(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "class Plain:\n"
            "    def work(self):\n"
            "        pass\n"
            "def f():\n"
            "    p = Plain()\n"
            "    p.work()\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []

    def test_suppressed_with_reason(self, tmp_path):
        root = _tree(tmp_path, {
            "srv.py": _SRV,
            "use.py": (
                "from srv import ModelServer\n"
                "def f(x):\n"
                "    s = ModelServer()  # sparkdl-lint: allow[H11] -- "
                "process-lifetime server; atexit hook closes it\n"
                "    return s.submit(x)\n")})
        found = analyze_paths([root], rules=["H11"], cache_path=None)
        assert _unsup(found, "H11") == []
        assert len(_sup(found, "H11")) == 1


# ---------------------------------------------------------------------------
# H12 — exception-flow accounting


_SERVE_PATH = "sparkdl_tpu/serve/fake_dispatch.py"


class TestH12ExceptionFlow:
    def test_pass_swallow_in_serve_path(self):
        src = ("def dispatch(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n"
               "        pass\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        assert len(_unsup(found, "H12")) == 1

    def test_log_only_swallow(self):
        src = ("import logging\n"
               "logger = logging.getLogger(__name__)\n"
               "def dispatch(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n"
               "        logger.exception('dispatch failed')\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        hits = _unsup(found, "H12")
        assert len(hits) == 1
        assert "log-only" in hits[0].message

    def test_chained_getlogger_swallow_is_caught(self):
        """`logging.getLogger(__name__).warning(...)` — the repo's own
        degrade idiom — is a log-only swallow; the chained receiver
        (a Call, invisible to _dotted) must still classify."""
        src = ("import logging\n"
               "def dispatch(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n"
               "        logging.getLogger(__name__).warning('x')\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        assert len(_unsup(found, "H12")) == 1

    def test_path_scope_holds_for_cwd_relative_paths(self, tmp_path,
                                                     monkeypatch):
        """Linting `obs/x.py` from INSIDE the package dir must not
        silently skip the path-scoped rule — the absolute form is
        consulted too."""
        pkg_obs = tmp_path / "sparkdl_tpu" / "obs"
        pkg_obs.mkdir(parents=True)
        (pkg_obs / "x.py").write_text(
            "def f(q):\n"
            "    try:\n"
            "        q.pop()\n"
            "    except Exception:\n"
            "        pass\n")
        monkeypatch.chdir(tmp_path / "sparkdl_tpu")
        found = analyze_paths(["obs"], rules=["H12"], cache_path=None)
        assert len(_unsup(found, "H12")) == 1, \
            [f.render() for f in found]

    def test_bare_continue_swallow(self):
        src = ("def drain(items):\n"
               "    for it in items:\n"
               "        try:\n"
               "            it.flush()\n"
               "        except Exception:\n"
               "            continue\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        hits = _unsup(found, "H12")
        assert len(hits) == 1
        assert "continue" in hits[0].message

    def test_counter_recording_form_is_accepted(self):
        """THE acceptance negative: the handler records a failure
        counter — the PR-7 population-separation contract satisfied."""
        src = ("from sparkdl_tpu.obs.registry import default_registry\n"
               "def dispatch(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n"
               "        default_registry().counter("
               "'serve.failures').add()\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        assert _unsup(found, "H12") == []

    @pytest.mark.parametrize("handler", [
        "        raise\n",
        "        return None\n",
        "        out['error'] = 'boom'\n",
        "        fut.set_exception(ValueError('x'))\n",
        "        slo_tracker().record(ok=False)\n",
    ], ids=["reraise", "return", "assign", "set-exception", "slo"])
    def test_accountable_handlers_are_clean(self, handler):
        src = ("def dispatch(q, out, fut, slo_tracker):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n" + handler)
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        assert _unsup(found, "H12") == [], \
            [f.render() for f in _unsup(found, "H12")]

    def test_outside_hot_paths_is_out_of_scope(self):
        src = ("def load(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    except Exception:\n"
               "        pass\n")
        found = analyze_source(src, "sparkdl_tpu/data/loader.py",
                               rules=["H12"])
        assert found == []

    def test_suppressed_with_reason(self):
        src = ("def dispatch(q):\n"
               "    try:\n"
               "        q.pop()\n"
               "    # sparkdl-lint: allow[H12] -- empty-queue race is "
               "the normal idle path, not a failure\n"
               "    except IndexError:\n"
               "        pass\n")
        found = analyze_source(src, _SERVE_PATH, rules=["H12"])
        assert _unsup(found, "H12") == []
        sup = _sup(found, "H12")
        assert len(sup) == 1
        assert "idle path" in sup[0].suppression


# ---------------------------------------------------------------------------
# fix-on-find regressions (the counters the sweep added)


class TestFixOnFindRegressions:
    def test_watchdog_monitor_error_is_counted(self):
        from sparkdl_tpu.obs.registry import default_registry
        from sparkdl_tpu.obs.watchdog import watchdog
        wd = watchdog()
        reg = default_registry()
        before = reg.snapshot().get("watchdog.monitor_errors", 0)
        orig = wd.check_once
        wd.check_once = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("injected monitor failure"))
        try:
            wd.arm(threshold_s=0.05)
            deadline = time.perf_counter() + 5.0
            while reg.snapshot().get("watchdog.monitor_errors",
                                     0) <= before:
                assert time.perf_counter() < deadline, \
                    "monitor error never counted"
                time.sleep(0.01)
        finally:
            wd.check_once = orig
            wd.disarm()
        assert reg.snapshot()["watchdog.monitor_errors"] > before

    def test_telemetry_handler_failure_is_counted(self):
        import urllib.error
        import urllib.request
        from sparkdl_tpu.obs.export import start_telemetry
        from sparkdl_tpu.obs.registry import default_registry
        reg = default_registry()
        tel = start_telemetry()
        try:
            before = reg.snapshot().get("telemetry.errors", 0)
            tel._statusz = lambda *a: (_ for _ in ()).throw(
                RuntimeError("injected statusz failure"))
            try:
                with urllib.request.urlopen(tel.url("/statusz"),
                                            timeout=5) as r:
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            assert code == 500
            assert reg.snapshot()["telemetry.errors"] > before
        finally:
            tel.close()

    def test_justified_swallow_is_suppressed_not_invisible(self):
        """The watchdog's best-effort flight dump swallow must appear
        as a SUPPRESSED H12 with its justification."""
        found = analyze_paths(
            [os.path.join(PKG_DIR, "obs", "watchdog.py")],
            rules=["H12"], cache_path=None)
        sup = _sup(found, "H12")
        assert any("IS accounted" in f.suppression for f in sup), \
            [f.render() for f in found]


# ---------------------------------------------------------------------------
# SARIF 2.1.0 output


def _validate_sarif(doc: dict) -> None:
    """Structural SARIF 2.1.0 validation (the schema's required
    properties for the subset sparkdl-lint emits)."""
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
    assert isinstance(doc["runs"], list) and len(doc["runs"]) == 1
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "sparkdl-lint"
    rule_ids = {r["id"] for r in driver["rules"]}
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
    assert isinstance(run["results"], list)
    for res in run["results"]:
        assert res["ruleId"] in rule_ids, \
            "result references an unlisted rule"
        assert res["level"] in ("none", "note", "warning", "error")
        assert res["message"]["text"]
        [loc] = res["locations"]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uri"]
        assert phys["region"]["startLine"] >= 1
        for sup in res.get("suppressions", ()):
            assert sup["kind"] in ("inSource", "external")


class TestSarif:
    def test_document_schema_and_suppressions(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def fine():\n"
            "    jax.device_get(1)  # sparkdl-lint: allow[H1] -- test\n"
            "def bad():\n"
            "    jax.device_get(2)\n")})
        found = analyze_paths([root], cache_path=None)
        doc = to_sarif(found, ALL_RULES)
        _validate_sarif(doc)
        results = doc["runs"][0]["results"]
        by_supp = [r for r in results if "suppressions" in r]
        assert len(by_supp) == 1
        assert "test" in by_supp[0]["suppressions"][0]["justification"]
        assert any("suppressions" not in r for r in results)
        # the full thirteen-rule catalogue rides in the driver
        ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
        assert {"H1", "H10", "H11", "H12"} <= ids

    def test_cli_sarif_round_trip(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def bad():\n"
            "    jax.device_get(2)\n")})
        out = tmp_path / "out.sarif"
        r = _run_cli("--no-cache", "--sarif", str(out), root)
        assert r.returncode == 1, (r.stdout, r.stderr)
        doc = json.loads(out.read_text())
        _validate_sarif(doc)
        assert len(doc["runs"][0]["results"]) == 1
        assert "SARIF" in r.stderr

    def test_ci_emits_schema_validated_sarif_for_the_package(
            self, tmp_path):
        """The CI-shaped invocation: package dir, SARIF out — the
        document must validate and carry only suppressed results."""
        out = tmp_path / "pkg.sarif"
        r = _run_cli("--sarif", str(out), "--no-cache",
                     os.path.join(PKG_DIR, "analysis"))
        assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
        _validate_sarif(json.loads(out.read_text()))


# ---------------------------------------------------------------------------
# --changed-only


class TestChangedOnly:
    def _git(self, cwd, *args):
        return subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             *args], cwd=cwd, capture_output=True, text=True)

    def test_dirty_file_detection(self, tmp_path):
        from sparkdl_tpu.analysis.__main__ import _git_dirty_files
        if self._git(tmp_path, "init").returncode != 0:
            pytest.skip("git unavailable")
        (tmp_path / "clean.py").write_text("x = 1\n")
        (tmp_path / "dirty.py").write_text("y = 1\n")
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-m", "seed")
        (tmp_path / "dirty.py").write_text("y = 2\n")
        (tmp_path / "fresh.py").write_text("z = 1\n")
        got = _git_dirty_files(str(tmp_path))
        names = sorted(os.path.basename(p) for p in got)
        assert names == ["dirty.py", "fresh.py"]

    def test_paths_anchor_at_the_git_toplevel(self, tmp_path):
        """Porcelain paths are toplevel-relative: a package vendored
        in a SUBDIRECTORY of a larger repo must still resolve its
        dirty files to real paths (a silent [] here made the --fast
        loop false-green)."""
        from sparkdl_tpu.analysis.__main__ import _git_dirty_files
        if self._git(tmp_path, "init").returncode != 0:
            pytest.skip("git unavailable")
        sub = tmp_path / "vendor" / "pkg"
        sub.mkdir(parents=True)
        (sub / "mod.py").write_text("x = 1\n")
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-m", "seed")
        (sub / "mod.py").write_text("x = 2\n")
        got = _git_dirty_files(str(sub))      # root BELOW the toplevel
        assert got and all(os.path.isfile(p) for p in got), got
        assert os.path.basename(got[0]) == "mod.py"

    def test_outside_checkout_returns_none(self, tmp_path):
        from sparkdl_tpu.analysis.__main__ import _git_dirty_files
        # tmp_path is not a git repo (and not inside one)
        assert _git_dirty_files(str(tmp_path)) is None

    def test_cli_smoke_exits_zero_on_clean_or_dirty_tree(self):
        """The pre-commit loop's contract: a lint-clean repo exits 0
        under --changed-only whether or not anything is dirty — and
        --json ALWAYS emits a parseable document, nothing-changed
        included (a consumer json.loads()ing stdout must never
        crash)."""
        r = _run_cli("--changed-only", "--no-cache", "--json")
        assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
        d = json.loads(r.stdout)
        assert d["unsuppressed"] == 0
        for key in ("findings", "suppressed", "rules", "by_rule",
                    "targets", "cache"):
            assert key in d, sorted(d)


# ---------------------------------------------------------------------------
# cache invalidation across analyzer-version bumps


class TestCacheVersionBump:
    def _paths(self, tmp_path):
        root = _tree(tmp_path, {
            "a.py": "def f():\n    pass\n",
            "b.py": "def g():\n    pass\n"})
        return root, str(tmp_path / "cache.json")

    def test_version_bump_forces_cold_reanalysis(self, tmp_path,
                                                 monkeypatch):
        """A facts-schema (analyzer version) bump must invalidate
        EVERY cached entry — file content and rule set are unchanged,
        so only the version key can force the cold pass."""
        root, cache = self._paths(tmp_path)
        stats: dict = {}
        analyze_paths([root], cache_path=cache, cache_stats=stats)
        assert stats["misses"] == 2 and stats["hits"] == 0
        stats = {}
        analyze_paths([root], cache_path=cache, cache_stats=stats)
        assert stats["hits"] == 2 and stats["misses"] == 0
        monkeypatch.setattr(cache_mod, "ANALYZER_VERSION",
                            cache_mod.ANALYZER_VERSION + 1)
        stats = {}
        analyze_paths([root], cache_path=cache, cache_stats=stats)
        assert stats["misses"] == 2 and stats["hits"] == 0, \
            "version bump did not force a cold re-analysis"

    def test_bumped_cache_rewrites_under_new_version(self, tmp_path,
                                                     monkeypatch):
        root, cache = self._paths(tmp_path)
        analyze_paths([root], cache_path=cache)
        monkeypatch.setattr(cache_mod, "ANALYZER_VERSION",
                            cache_mod.ANALYZER_VERSION + 1)
        analyze_paths([root], cache_path=cache)
        stats: dict = {}
        analyze_paths([root], cache_path=cache, cache_stats=stats)
        assert stats["hits"] == 2, \
            "re-analysis under the new version did not repopulate"

    def test_effect_facts_survive_the_cache_round_trip(self, tmp_path):
        """Cached effect facts must reproduce the same H10 verdicts —
        the serialization is part of the facts schema."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "def eff(reg):\n"
            "    reg.counter('a.b').add()\n"
            "@jax.jit\n"
            "def step(x, reg):\n"
            "    return eff(reg)\n")})
        cache = str(tmp_path / "c.json")
        cold = analyze_paths([root], rules=["H10"], cache_path=cache)
        stats: dict = {}
        warm = analyze_paths([root], rules=["H10"], cache_path=cache,
                             cache_stats=stats)
        assert stats["hits"] == 1
        assert [f.message for f in _unsup(cold, "H10")] == \
            [f.message for f in _unsup(warm, "H10")]


# ---------------------------------------------------------------------------
# meta: the nineteen-rule acceptance gate


class TestMetaNineteenRules:
    def test_all_rules_includes_the_effect_system(self):
        assert {"H10", "H11", "H12", "H13", "H14", "H15",
                "H16", "H17", "H18", "H19"} <= set(ALL_RULES)
        assert len(ALL_RULES) == 19

    def test_package_tools_examples_clean_under_nineteen_rules(self):
        """THE acceptance gate: zero unsuppressed findings under all
        nineteen rules across the package + tools/ + examples/."""
        targets = [PKG_DIR]
        for extra in ("tools", "examples"):
            d = os.path.join(REPO_ROOT, extra)
            if os.path.isdir(d):
                targets.append(d)
        found = analyze_paths(targets, cache_path=None)
        unsup = [f for f in found if not f.suppressed]
        assert unsup == [], "\n".join(f.render() for f in unsup)

    def test_real_package_jit_roots_are_detected(self):
        """The effect system must SEE the package's actual jit
        boundaries — including the streaming estimator's step defined
        inside an epoch loop (the walk-depth fix)."""
        from sparkdl_tpu.analysis import iter_python_files
        g = build_graph(list(iter_python_files(
            os.path.join(PKG_DIR, "estimators"))))
        roots = {k for m in g.modules.values()
                 for k, fe in m.effects.items() if fe.jitted}
        assert any("_run_full_batch" in k for k in roots), roots
        assert any("_run_streaming" in k for k in roots), roots

    def test_h12_fixes_are_part_of_the_record(self):
        """The sweep's accounting counters exist in the source the
        rules gate (a refactor dropping them re-opens the H12 hole)."""
        with open(os.path.join(PKG_DIR, "obs", "watchdog.py")) as f:
            assert "watchdog.monitor_errors" in f.read()
        with open(os.path.join(PKG_DIR, "obs", "export.py")) as f:
            assert "telemetry.errors" in f.read()
