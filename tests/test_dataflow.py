"""Device-dataflow layer tests: hot-path classification over the real
package, the H14 hot-sync rule (witness chains through resolved call
edges), the H15 donation rule's dead-vs-escaping argument matrix, the
H16 widening rule, cache round-trip of the dataflow facts, the
analyzer's per-rule cost accounting, and the ISSUE-12 fix-on-find
regressions (the estimator's donated batch, the LR estimators'
epoch-boundary loss drains).

Fixture style mirrors tests/test_callgraph.py / test_effects.py:
deliberately hazardous multi-module trees under tmp_path trip the
rules; the idiomatic clean forms don't; inline suppressions downgrade
without hiding. Hot fixtures mark their loops the same way the repo
does — a ``sparkdl_tpu.obs.watchdog`` watch/pulse import + call — so
hotness is detected lexically, never by executing fixture code.
"""

import os

import numpy as np
import pytest

import sparkdl_tpu
from sparkdl_tpu.analysis import analyze_paths, build_graph
from sparkdl_tpu.analysis.callgraph import ModuleFacts, scan_module
from sparkdl_tpu.analysis.dataflow import DeviceFlow, _flow_state
from sparkdl_tpu.analysis.walker import analyze_source
import ast

PKG_DIR = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))
REPO_ROOT = os.path.dirname(PKG_DIR)

WATCH_IMPORT = \
    "from sparkdl_tpu.obs.watchdog import watch as watchdog_watch\n"


def _tree(tmp_path, files: dict) -> str:
    tmp_path.mkdir(parents=True, exist_ok=True)
    for name, src in files.items():
        (tmp_path / name).write_text(src)
    return str(tmp_path)


def _unsup(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


# ---------------------------------------------------------------------------
# hot-path classification


class TestHotPathClassification:
    def test_watchdog_marker_roots_a_function(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m.loop'):\n"
            "            pass\n"
            "def cold(xs):\n"
            "    return xs\n")})
        g = build_graph([os.path.join(root, "m.py")])
        state = _flow_state(g)
        [loop_key] = [k for k in g.functions if k.endswith("::loop")]
        [cold_key] = [k for k in g.functions if k.endswith("::cold")]
        assert state.hot.is_hot(loop_key)
        assert not state.hot.is_hot(cold_key)

    def test_hotness_flows_down_not_up(self, tmp_path):
        """Callees of a hot loop are hot (with a recorded chain);
        the loop's own CALLERS are not."""
        root = _tree(tmp_path, {"m.py": (
            WATCH_IMPORT +
            "def helper(x):\n"
            "    return x\n"
            "def loop(xs):\n"
            "    with watchdog_watch('m'):\n"
            "        for x in xs:\n"
            "            helper(x)\n"
            "def caller(xs):\n"
            "    loop(xs)\n")})
        g = build_graph([os.path.join(root, "m.py")])
        state = _flow_state(g)
        key = {k.rsplit("::", 1)[1]: k for k in g.functions}
        assert state.hot.is_hot(key["helper"])
        assert not state.hot.is_hot(key["caller"])
        chain = state.hot.chain(key["helper"])
        assert chain[0] == key["loop"] and chain[-1] == key["helper"]
        assert "loop -> " in state.hot.why(key["helper"])

    def test_real_package_roots_are_hot(self):
        """The runner dispatch/drain state machine, the serve
        dispatcher, the engine stream/re-chunk path, and the
        estimator step loops all classify hot on the real package."""
        from sparkdl_tpu.analysis import iter_python_files
        g = build_graph(list(iter_python_files(PKG_DIR)))
        state = _flow_state(g)
        hot = {k for k in g.functions if state.hot.is_hot(k)}

        def has(qual):
            return any(k.endswith("::" + qual) for k in hot), \
                sorted(q for q in hot if qual.split(".")[-1] in q)

        for qual in ("dispatch_chunks", "drain_bounded",
                     "SlabSink.write",
                     "ModelSession._serve_loop",
                     "LocalEngine._stream_rechunk",
                     "KerasImageFileEstimator._trainOne",
                     "LogisticRegression._run_minibatch"):
            ok, near = has(qual)
            assert ok, (qual, near)

    def test_tools_examples_and_config_paths_are_cold(self):
        """Hotness must not leak UP into the CLIs that call the hot
        paths, nor into cold config/constructor code."""
        from sparkdl_tpu.analysis import iter_python_files
        paths = list(iter_python_files(PKG_DIR))
        for extra in ("tools", "examples"):
            d = os.path.join(REPO_ROOT, extra)
            if os.path.isdir(d):
                paths.extend(iter_python_files(d))
        g = build_graph(paths)
        state = _flow_state(g)
        for key in g.functions:
            mod = key.partition("::")[0]
            if mod.startswith(("tools.", "examples.")) \
                    or ".serve.config" in mod:
                assert not state.hot.is_hot(key), \
                    (key, state.hot.why(key))


# ---------------------------------------------------------------------------
# H14 — hot-path host sync


class TestH14HotPathSync:
    def _analyze(self, root):
        return analyze_paths([root], cache_path=None)

    def test_item_sync_in_hot_loop_caught(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            v = jnp.asarray(x)\n"
            "            out.append(v.item())\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1 and "`.item()`" in h14[0].message, \
            [f.render() for f in h14]

    def test_witness_chain_through_two_modules(self, tmp_path):
        """The sync sits two resolved call edges from the watchdog
        root, with the device value crossing as an ARGUMENT — the
        finding anchors in the leaf module and prints the full hot
        chain module-by-module."""
        root = _tree(tmp_path, {
            "sink.py": ("def record(loss, out):\n"
                        "    out.append(float(loss))\n"),
            "mid.py": ("from sink import record\n"
                       "def forward(loss, out):\n"
                       "    record(loss, out)\n"),
            "hot.py": ("import jax.numpy as jnp\n" + WATCH_IMPORT +
                       "from mid import forward\n"
                       "def drive(xs, out):\n"
                       "    for x in xs:\n"
                       "        with watchdog_watch('hot'):\n"
                       "            loss = jnp.asarray(x)\n"
                       "            forward(loss, out)\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1, [f.render() for f in h14]
        f = h14[0]
        assert f.path.endswith("sink.py")
        # the chain prints module-by-module, root first (module names
        # carry the fixture dir prefix)
        assert "hot:drive -> " in f.message, f.message
        assert "mid:forward -> " in f.message, f.message
        assert "sink:record" in f.message, f.message
        assert f.message.index("hot:drive") \
            < f.message.index("mid:forward") \
            < f.message.index("sink:record")
        assert "`float(...)`" in f.message

    @pytest.mark.parametrize("sync", [
        "float(v)", "int(v)", "len(v)", "np.asarray(v)",
        "v.tolist()"])
    def test_materialization_forms_caught(self, tmp_path, sync):
        root = _tree(tmp_path, {"m.py": (
            "import numpy as np\n"
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            v = jnp.asarray(x)\n"
            f"            out.append({sync})\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1, (sync, [f.render() for f in h14])

    def test_truthiness_and_iteration_caught(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    with watchdog_watch('m'):\n"
            "        v = jnp.asarray(xs)\n"
            "        if v:\n"
            "            out.append(1)\n"
            "        for row in v:\n"
            "            out.append(row)\n")})
        h14 = _unsup(self._analyze(root), "H14")
        forms = {f.message.split(" over ")[0] for f in h14}
        assert len(h14) == 2, [f.render() for f in h14]
        assert any("truth" in m for m in forms), forms
        assert any("for ... in" in m for m in forms), forms

    def test_aliased_module_import_resolves(self, tmp_path):
        """Review regression: device-ness must cross `import mod as
        alias` calls — the dotted qualifier carries the IMPORT SOURCE
        (the locks.py contract), not the local alias."""
        root = _tree(tmp_path, {
            "helpers_mod.py": ("import jax.numpy as jnp\n"
                               "def make(x):\n"
                               "    return jnp.asarray(x)\n"),
            "main_mod.py": ("import helpers_mod as hm\n"
                            + WATCH_IMPORT +
                            "def loop(xs, out):\n"
                            "    for x in xs:\n"
                            "        with watchdog_watch('m'):\n"
                            "            v = hm.make(x)\n"
                            "            out.append(v.item())\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1 and "`v`" in h14[0].message, \
            [f.render() for f in h14]

    def test_self_call_resolves_despite_ambiguous_method_name(
            self, tmp_path):
        """Review regression: `self.make()` binds to the ENCLOSING
        class even when another class defines a same-named method —
        the qualifier carries the class, not the unique-method
        fallback."""
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "class A:\n"
            "    def make(self, x):\n"
            "        return jnp.asarray(x)\n"
            "    def drive(self, xs, out):\n"
            "        for x in xs:\n"
            "            with watchdog_watch('m'):\n"
            "                v = self.make(x)\n"
            "                out.append(v.item())\n"
            "class B:\n"
            "    def make(self, x):\n"
            "        return x\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1 and "`v`" in h14[0].message, \
            [f.render() for f in h14]

    def test_cold_function_not_flagged(self, tmp_path):
        """The same sync OFF the hot set is fine — draining at a
        boundary is exactly what the fix-on-find sweep installed."""
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n"
            "def summarize(xs):\n"
            "    v = jnp.asarray(xs)\n"
            "    return float(v)\n")})
        assert _unsup(self._analyze(root), "H14") == []

    def test_container_of_device_arrays_not_flagged(self, tmp_path):
        """Review regression: a host LIST of device arrays is a plain
        python container — len()/iteration over it are free host ops,
        exactly the pre-staging pattern the rule should encourage."""
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(data, step):\n"
            "    with watchdog_watch('m'):\n"
            "        batches = [jnp.asarray(b) for b in data]\n"
            "        if len(batches) > 1:\n"
            "            pass\n"
            "        for xb in batches:\n"
            "            step(xb)\n")})
        assert _unsup(self._analyze(root), "H14") == []

    def test_len_message_is_honest_about_metadata(self, tmp_path):
        """len() on a jax array reads static shape — the finding must
        not claim the thread blocks."""
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    with watchdog_watch('m'):\n"
            "        v = jnp.asarray(xs)\n"
            "        out.append(len(v))\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1, [f.render() for f in h14]
        assert "static metadata" in h14[0].message
        assert "blocks until the device" not in h14[0].message

    def test_arithmetic_propagates_device_ness(self, tmp_path):
        """Review regression: `y = dev * dev` is a device array — the
        per-step `.item()` on the DERIVED value must still flag."""
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            dev = jnp.asarray(x)\n"
            "            y = dev * dev\n"
            "            out.append(y.item())\n")})
        h14 = _unsup(self._analyze(root), "H14")
        assert len(h14) == 1 and "`y`" in h14[0].message, \
            [f.render() for f in h14]

    def test_host_values_not_flagged(self, tmp_path):
        """np/host values materialize freely — only device-tracked
        values count."""
        root = _tree(tmp_path, {"m.py": (
            "import numpy as np\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            v = np.square(x)\n"
            "            out.append(float(v))\n")})
        assert _unsup(self._analyze(root), "H14") == []

    def test_inline_suppression_downgrades_not_hides(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            v = jnp.asarray(x)\n"
            "            out.append(v.item())  "
            "# sparkdl-lint: allow[H14] -- convergence check needs "
            "the scalar per step\n")})
        found = [f for f in self._analyze(root) if f.rule == "H14"]
        assert len(found) == 1 and found[0].suppressed
        assert "convergence" in found[0].suppression

    def test_sanctioned_drain_is_allowlisted_not_invisible(self):
        """timed_device_get's own scope may materialize — via the
        DEFAULT_ALLOWLIST H14 entry, reported suppressed."""
        found = analyze_source(
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def timed_device_get(res):\n"
            "    with watchdog_watch('drain'):\n"
            "        v = jnp.asarray(res)\n"
            "        return v.item()\n",
            "sparkdl_tpu/obs/trace.py", rules=["H14"])
        h14 = [f for f in found if f.rule == "H14"]
        assert h14 and all(f.suppressed for f in h14)
        assert "allowlist" in h14[0].suppression


# ---------------------------------------------------------------------------
# H15 — missing buffer donation: the dead-vs-escaping matrix


_H15_HEADER = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "def run(step, X, keep):\n"
    "    jitted = jax.jit(step)\n"
    "    state = jnp.zeros((4,), jnp.float32)\n")


class TestH15Donation:
    def _h15(self, tmp_path, body, header=_H15_HEADER):
        root = _tree(tmp_path, {"m.py": header + body})
        return _unsup(analyze_paths([root], cache_path=None), "H15")

    def test_dead_argument_caught_with_index(self, tmp_path):
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "    return state\n")
        assert len(h15) == 1, [f.render() for f in h15]
        assert "`xb`" in h15[0].message
        assert "donate_argnums=(1,)" in h15[0].message

    def test_result_carrying_state_not_flagged(self, tmp_path):
        """``state`` is read after the call (returned, re-fed) — its
        buffer is NOT dead, donation analysis must skip it."""
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "    return state\n")
        assert not any("`state`" in f.message for f in h15)

    @pytest.mark.parametrize("escape,why", [
        ("        keep.append(xb)\n", "passed to another call"),
        ("        keep.attr = xb\n", "stored on an attribute"),
        ("        keep[i] = xb\n", "stored in a container"),
    ], ids=["arg-pass", "attr-store", "subscript-store"])
    def test_escaping_argument_not_flagged(self, tmp_path, escape,
                                           why):
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        + escape +
                        "        state = jitted(state, xb)\n"
                        "    return state\n")
        assert h15 == [], (why, [f.render() for f in h15])

    def test_read_after_call_not_flagged(self, tmp_path):
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "        last = xb\n"
                        "    return state, last\n")
        assert h15 == [], [f.render() for f in h15]

    def test_loop_carried_argument_not_flagged(self, tmp_path):
        """A buffer placed BEFORE the loop and re-fed every iteration
        is loop-carried — donating it would poison iteration 2."""
        h15 = self._h15(tmp_path,
                        "    xb = jnp.asarray(X)\n"
                        "    for i in range(8):\n"
                        "        state = jitted(state, xb)\n"
                        "    return state\n")
        assert h15 == [], [f.render() for f in h15]

    def test_parameter_argument_not_flagged(self, tmp_path):
        """A function PARAMETER's lifetime belongs to the caller —
        never dead from this scope's view."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def run_one(step, xb):\n"
            "    jitted = jax.jit(step)\n"
            "    return jitted(xb)\n")})
        assert _unsup(analyze_paths([root], cache_path=None),
                      "H15") == []

    def test_donated_compile_not_flagged(self, tmp_path):
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "    return state\n",
                        header=_H15_HEADER.replace(
                            "jax.jit(step)",
                            "jax.jit(step, donate_argnums=(1,))"))
        assert h15 == [], [f.render() for f in h15]

    def test_jit_compiled_in_resolved_helper_caught(self, tmp_path):
        """The estimator shape: the jit is compiled by a helper and
        returned; the call site is where donation analysis runs — the
        finding names the compiling call."""
        root = _tree(tmp_path, {
            "compiler.py": ("import jax\n"
                            "def compile_step(step):\n"
                            "    jitted = jax.jit(step)\n"
                            "    return jitted, 32\n"),
            "trainer.py": ("import jax.numpy as jnp\n"
                           "from compiler import compile_step\n"
                           "def train(step, X):\n"
                           "    jitted, bs = compile_step(step)\n"
                           "    for i in range(8):\n"
                           "        xb = jnp.asarray(X[i])\n"
                           "        out = jitted(xb)\n"
                           "    return out\n")})
        h15 = _unsup(analyze_paths([root], cache_path=None), "H15")
        assert len(h15) == 1, [f.render() for f in h15]
        assert h15[0].path.endswith("trainer.py")
        assert "compile_step" in h15[0].message
        assert "donate_argnums=(0,)" in h15[0].message

    def test_model_function_jitted_form(self, tmp_path):
        """`mf.jitted()` declares no donation: a dead batch flags."""
        src = ("import jax.numpy as jnp\n"
               "def apply(mf, rows):\n"
               "    fn = mf.jitted()\n"
               "    d = jnp.asarray(rows)\n"
               "    return fn(d)\n")
        root = _tree(tmp_path, {"m.py": src})
        h15 = _unsup(analyze_paths([root], cache_path=None), "H15")
        assert len(h15) == 1 and "`d`" in h15[0].message, \
            [f.render() for f in h15]

    def test_inline_suppression(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            _H15_HEADER +
            "    for i in range(8):\n"
            "        xb = jnp.asarray(X[i])\n"
            "        # sparkdl-lint: allow[H15] -- xb aliases a "
            "caller-owned staging buffer\n"
            "        state = jitted(state, xb)\n"
            "    return state\n")})
        found = [f for f in analyze_paths([root], cache_path=None)
                 if f.rule == "H15"]
        assert len(found) == 1 and found[0].suppressed
        assert "staging buffer" in found[0].suppression

    def test_nonlocal_rebinding_closure_is_an_escape(self, tmp_path):
        """Review regression: a nested def that rebinds the buffer
        via `nonlocal` both reads and writes the OUTER binding — the
        buffer is captured, not dead, and donating it would be a
        use-after-donate when the closure later runs."""
        h15 = self._h15(tmp_path,
                        "    xb = jnp.asarray(X)\n"
                        "    def reset():\n"
                        "        nonlocal xb\n"
                        "        xb = jnp.zeros_like(xb)\n"
                        "    keep.append(reset)\n"
                        "    state = jitted(state, xb)\n"
                        "    return state\n")
        assert h15 == [], [f.render() for f in h15]

    def test_conditionally_assigned_loop_buffer_not_flagged(
            self, tmp_path):
        """Review regression: an arg assigned on a maybe-skipped
        branch inside the loop is reused across the back-edge by the
        iterations that skip it — loop-carried, never dead."""
        h15 = self._h15(tmp_path,
                        "    xb = jnp.asarray(X[0])\n"
                        "    for i in range(8):\n"
                        "        if i % 2 == 0:\n"
                        "            xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "    return state\n")
        assert h15 == [], [f.render() for f in h15]

    def test_reassignment_after_the_call_keeps_the_finding(
            self, tmp_path):
        """Review regression: deadness is judged against the
        assignment REACHING the call (snapshotted at call time) — a
        later conditional reassignment of the same name must not
        launder the verdict about the buffer fed into the call."""
        h15 = self._h15(tmp_path,
                        "    for i in range(8):\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "        if i == 7:\n"
                        "            xb = jnp.asarray(X[0])\n"
                        "    return state\n")
        assert any("`xb`" in f.message for f in h15), \
            [f.render() for f in h15]

    def test_back_edge_read_above_the_assignment_not_flagged(
            self, tmp_path):
        """Review regression: a read at the loop TOP, lexically above
        the reaching assignment, runs on the next iteration against
        this iteration's buffer — donating it would crash iteration
        2 with a use-after-donate."""
        h15 = self._h15(tmp_path,
                        "    xb = jnp.asarray(X[0])\n"
                        "    delta = jnp.zeros((4,), jnp.float32)\n"
                        "    for i in range(8):\n"
                        "        delta = delta + xb\n"
                        "        xb = jnp.asarray(X[i])\n"
                        "        state = jitted(state, xb)\n"
                        "    return state, delta\n")
        assert not any("`xb`" in f.message for f in h15), \
            [f.render() for f in h15]

    def test_device_container_arg_still_flagged(self, tmp_path):
        """A dict comprehension of device arrays is a donatable
        pytree — the ModelFunction.__call__ shape."""
        root = _tree(tmp_path, {"m.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def apply_once(step, rows):\n"
            "    jitted = jax.jit(step)\n"
            "    d = {k: jnp.asarray(v) for k, v in rows.items()}\n"
            "    return jitted(d)\n")})
        h15 = _unsup(analyze_paths([root], cache_path=None), "H15")
        assert len(h15) == 1 and "`d`" in h15[0].message, \
            [f.render() for f in h15]


# ---------------------------------------------------------------------------
# H16 — dtype widening


class TestH16Widening:
    def _h16(self, tmp_path, line):
        root = _tree(tmp_path, {"m.py": (
            "import numpy as np\n"
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def ship(chunks, out):\n"
            "    for c in chunks:\n"
            "        with watchdog_watch('m'):\n"
            "            dev = jnp.asarray(c)\n"
            f"            {line}\n"
            "            out.append(dev)\n")})
        return _unsup(analyze_paths([root], cache_path=None), "H16")

    def test_dtypeless_zeros_caught(self, tmp_path):
        h16 = self._h16(tmp_path, "dev = dev + np.zeros(4)")
        assert len(h16) == 1 and "np.zeros" in h16[0].message, \
            [f.render() for f in h16]
        assert "hot witness" in h16[0].message

    def test_float64_scalar_caught(self, tmp_path):
        h16 = self._h16(tmp_path, "dev = dev * np.float64(0.5)")
        assert len(h16) == 1, [f.render() for f in h16]

    def test_dtypeless_full_caught(self, tmp_path):
        """Review regression: np.full's dtype is the THIRD positional
        — the two-arg form is dtype-less and must flag; the
        dtype-pinned form must not."""
        h16 = self._h16(tmp_path, "dev = dev + np.full((4,), 0.5)")
        assert len(h16) == 1, [f.render() for f in h16]
        clean = self._h16(
            tmp_path / "b",
            "dev = dev + np.full((4,), 0.5, np.float32)")
        assert clean == [], [f.render() for f in clean]

    def test_float_literal_caught(self, tmp_path):
        h16 = self._h16(tmp_path, "dev = dev * 2.5")
        assert len(h16) == 1, [f.render() for f in h16]

    def test_pinned_dtype_not_flagged(self, tmp_path):
        h16 = self._h16(tmp_path,
                        "dev = dev + np.zeros(4, dtype=np.float32)")
        assert h16 == [], [f.render() for f in h16]

    def test_cold_function_not_flagged(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import numpy as np\n"
            "import jax.numpy as jnp\n"
            "def summarize(c):\n"
            "    dev = jnp.asarray(c)\n"
            "    return dev + np.zeros(4)\n")})
        assert _unsup(analyze_paths([root], cache_path=None),
                      "H16") == []

    def test_inline_suppression(self, tmp_path):
        h16 = [f for f in analyze_paths([_tree(tmp_path, {"m.py": (
            "import numpy as np\n"
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def ship(chunks, out):\n"
            "    for c in chunks:\n"
            "        with watchdog_watch('m'):\n"
            "            dev = jnp.asarray(c)\n"
            "            dev = dev + np.zeros(4)  "
            "# sparkdl-lint: allow[H16] -- f64 accumulator is the "
            "numerically-required reduction dtype\n"
            "            out.append(dev)\n")})], cache_path=None)
            if f.rule == "H16"]
        assert len(h16) == 1 and h16[0].suppressed


# ---------------------------------------------------------------------------
# facts serialization + cache + cost accounting


class TestFactsAndCost:
    def test_device_flow_round_trips_through_module_facts(self):
        src = ("import jax\n"
               "import jax.numpy as jnp\n" + WATCH_IMPORT +
               "def loop(xs, out):\n"
               "    jitted = jax.jit(len)\n"
               "    for x in xs:\n"
               "        with watchdog_watch('m'):\n"
               "            v = jnp.asarray(x)\n"
               "            out.append(v.item())\n")
        mf = scan_module(ast.parse(src), "m.py")
        back = ModuleFacts.from_dict(mf.to_dict())
        assert set(back.flows) == set(mf.flows)
        for key, flow in mf.flows.items():
            b = back.flows[key]
            assert isinstance(b, DeviceFlow)
            assert b.hot_root == flow.hot_root
            assert b.params == flow.params
            assert b.last_load == flow.last_load
            assert [(e.kind, e.line, e.loops, e.data)
                    for e in b.events] == \
                [(e.kind, e.line, e.loops, e.data)
                 for e in flow.events]

    def test_cached_rerun_reports_identical_h14(self, tmp_path):
        """The dataflow facts ride the per-file cache: a warm run
        replays them without re-scanning and reaches the same
        verdicts."""
        root = _tree(tmp_path / "t", {"m.py": (
            "import jax.numpy as jnp\n" + WATCH_IMPORT +
            "def loop(xs, out):\n"
            "    for x in xs:\n"
            "        with watchdog_watch('m'):\n"
            "            v = jnp.asarray(x)\n"
            "            out.append(v.item())\n")})
        cache = str(tmp_path / "cache.json")
        stats_cold: dict = {}
        cold = analyze_paths([root], cache_path=cache,
                             cache_stats=stats_cold)
        stats_warm: dict = {}
        warm = analyze_paths([root], cache_path=cache,
                             cache_stats=stats_warm)
        assert stats_cold["misses"] == 1 and stats_cold["hits"] == 0
        assert stats_warm["hits"] == 1 and stats_warm["misses"] == 0
        assert [f.message for f in _unsup(cold, "H14")] == \
            [f.message for f in _unsup(warm, "H14")]
        assert _unsup(warm, "H14"), "warm run lost the finding"

    def test_rule_stats_cover_the_dataflow_rules(self, tmp_path):
        root = _tree(tmp_path, {"m.py": "def f():\n    return 1\n"})
        rule_stats: dict = {}
        analyze_paths([root], cache_path=None, rule_stats=rule_stats)
        per_rule = rule_stats["per_rule_s"]
        for rule in ("H14", "H15", "H16", "H7", "H10", "scan"):
            assert rule in per_rule, (rule, sorted(per_rule))
            assert per_rule[rule] >= 0.0
        assert rule_stats["total_s"] > 0.0


# ---------------------------------------------------------------------------
# ISSUE-12 fix-on-find regressions


class TestFixOnFindRegressions:
    def test_estimator_step_donates_nothing(self):
        """XLA reuses a donated input only for a same-shaped output,
        and the train step returns state + a scalar loss: donating the
        batch args (the earlier H15 fix) was never usable and only
        produced JAX's "donated buffers were not usable" warning — on
        the v5e as on the CPU (chip_smoke.py's fit leg). Neither
        _compile_step branch donates."""
        path = os.path.join(PKG_DIR, "estimators",
                            "keras_image_file_estimator.py")
        with open(path) as f:
            src = f.read()
        assert "donate_argnums=" not in src

    def test_logistic_regression_drains_at_the_boundary(self):
        """The three per-step float(loss) syncs are gone: losses
        accumulate device-side and drain once per epoch/fit."""
        path = os.path.join(PKG_DIR, "estimators",
                            "logistic_regression.py")
        with open(path) as f:
            src = f.read()
        assert ".append(float(loss))" not in src, \
            "a per-step float(loss) sync came back"
        assert src.count("jax.device_get(losses)") >= 2

    def test_estimators_package_is_h14_h15_clean(self):
        found = analyze_paths([os.path.join(PKG_DIR, "estimators")],
                              cache_path=None)
        for rule in ("H14", "H15", "H16"):
            assert _unsup(found, rule) == [], \
                [f.render() for f in _unsup(found, rule)]

    def test_logistic_regression_history_still_floats(self):
        """Behavior pin for the drain refactor: objectiveHistory is
        plain python floats, one per iteration, finite."""
        import pyarrow as pa

        from sparkdl_tpu.data import DataFrame
        from sparkdl_tpu.data.tensors import append_tensor_column
        from sparkdl_tpu.estimators import LogisticRegression

        rng = np.random.default_rng(0)
        y = np.arange(16) % 2
        x = rng.normal(size=(16, 4)).astype(np.float32) \
            + 3.0 * y[:, None].astype(np.float32)
        b = pa.RecordBatch.from_pylist(
            [{"label": int(v)} for v in y])
        b = append_tensor_column(b, "features", x)
        model = LogisticRegression(maxIter=3).fit(
            DataFrame.from_batches([b]))
        hist = model.objectiveHistory
        assert len(hist) == 3
        assert all(isinstance(v, float) and np.isfinite(v)
                   for v in hist), hist
        assert hist[-1] <= hist[0], hist

    def test_model_function_call_suppression_is_visible(self):
        """The __call__ aliasing suppression must stay a REPORTED
        H15 suppression, never silently disappear."""
        found = analyze_paths(
            [os.path.join(PKG_DIR, "graph", "function.py")],
            cache_path=None)
        h15 = [f for f in found if f.rule == "H15"]
        assert any(f.suppressed and "alias" in f.suppression.lower()
                   for f in h15), [f.render() for f in h15]
