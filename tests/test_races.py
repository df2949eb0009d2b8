"""Static race detector tests: thread-topology inference + the
H17/H18/H19 guarded-by consistency rules, plus the runtime
cross-check (``assert_lock_owned`` under ``SPARKDL_TPU_SANITIZE=1``).

Fixture style mirrors tests/test_callgraph.py: deliberately racy
multi-module trees under tmp_path trip the rules WITH their full
witnesses (both thread roots, the lock identity, the guarded-by
evidence); the locked/atomic/double-checked clean forms stay silent;
inline suppressions downgrade without hiding. The real package is
pinned twice: its known concurrent loops must be IN the thread-root
inventory (a moved spawn site must not silently drop them) and the
whole package must be clean under the three rules — including the
three real fixes this sweep landed (server close, ledger verdict,
policy state code), each pinned by a source regression test.
"""

import os

import pytest

import sparkdl_tpu
from sparkdl_tpu.analysis import analyze_paths, build_graph
from sparkdl_tpu.analysis import cache as cache_mod
from sparkdl_tpu.analysis import iter_python_files
from sparkdl_tpu.analysis.races import _guard_model
from sparkdl_tpu.analysis.threads import thread_topology
from sparkdl_tpu.analysis.walker import ALL_RULES

PKG_DIR = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))
REPO_ROOT = os.path.dirname(PKG_DIR)

RACE_RULES = ["H17", "H18", "H19"]


def _tree(tmp_path, files: dict) -> str:
    for name, src in files.items():
        (tmp_path / name).write_text(src)
    return str(tmp_path)


def _unsup(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def _sup(findings, rule):
    return [f for f in findings if f.rule == rule and f.suppressed]


_package_graph_cache = {}


def _package_graph():
    """The full-package CallGraph, built once per test run (the
    topology + guard model memoize onto it)."""
    if "g" not in _package_graph_cache:
        _package_graph_cache["g"] = build_graph(
            list(iter_python_files(PKG_DIR)))
    return _package_graph_cache["g"]


# ---------------------------------------------------------------------------
# H17 — unguarded access to a guarded attribute


H17_RACY = (
    "import threading\n"
    "\n"
    "class Buf:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.items = []\n"
    "\n"
    "    def start(self):\n"
    "        t = threading.Thread(target=self.worker)\n"
    "        t.start()\n"
    "\n"
    "    def worker(self):\n"
    "        with self._lock:\n"
    "            self.items.append(1)\n"
    "\n"
    "    def size(self):\n"
    "        with self._lock:\n"
    "            return len(self.items)\n"
    "\n"
    "    def clear(self):\n"
    "        with self._lock:\n"
    "            self.items.clear()\n"
    "\n"
    "    def peek(self):\n"
    "        return self.items[0]\n")


class TestH17:
    def test_unguarded_read_fires_with_full_witness(self, tmp_path):
        root = _tree(tmp_path, {"m.py": H17_RACY})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H17")
        assert len(hits) == 1, [f.render() for f in hits]
        f = hits[0]
        assert f.qualname == "Buf.peek"
        # the witness: lock identity + majority evidence + BOTH
        # thread roots (the spawned worker and the implicit main)
        assert "m:Buf._lock" in f.message
        assert "majority evidence" in f.message
        assert "held at 5 of 6 accesses" in f.message
        assert "the main thread" in f.message
        assert "shares" in f.message and "instance state" in f.message

    def test_fully_locked_class_is_silent(self, tmp_path):
        src = H17_RACY.replace(
            "    def peek(self):\n"
            "        return self.items[0]\n",
            "    def peek(self):\n"
            "        with self._lock:\n"
            "            return self.items[0]\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H17") == []

    def test_single_threaded_class_is_exempt(self, tmp_path):
        # same racy shape, but NO spawn anywhere: one thread, no race
        src = H17_RACY.replace(
            "    def start(self):\n"
            "        t = threading.Thread(target=self.worker)\n"
            "        t.start()\n", "")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H17") == []

    def test_inline_suppression_downgrades_without_hiding(
            self, tmp_path):
        src = H17_RACY.replace(
            "        return self.items[0]\n",
            "        return self.items[0]  "
            "# sparkdl-lint: allow[H17] -- reader tolerates staleness\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H17") == []
        sup = _sup(found, "H17")
        assert len(sup) == 1
        assert "reader tolerates staleness" in sup[0].suppression

    def test_init_never_votes_and_is_never_flagged(self, tmp_path):
        # __init__ assigns without the lock at two sites; they must
        # neither dilute the vote nor be flagged themselves
        src = H17_RACY.replace(
            "        self.items = []\n",
            "        self.items = []\n"
            "        self.items.append(0)\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H17")
        assert len(hits) == 1
        assert hits[0].qualname == "Buf.peek"
        assert "held at 5 of 6 accesses" in hits[0].message

    def test_two_module_witness_chain(self, tmp_path):
        root = _tree(tmp_path, {
            "w.py": (
                "import threading\n"
                "\n"
                "class Shared:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.n = 0\n"
                "    def bump(self):\n"
                "        with self._lock:\n"
                "            self.n += 1\n"
                "    def sync_read(self):\n"
                "        with self._lock:\n"
                "            return self.n\n"
                "    def racy_read(self):\n"
                "        return self.n\n"
                "\n"
                "def run(obj):\n"
                "    obj.bump()\n"),
            "s.py": (
                "import threading\n"
                "from w import run\n"
                "\n"
                "def launch(obj):\n"
                "    t = threading.Thread(target=run, args=(obj,))\n"
                "    t.start()\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H17")
        assert len(hits) == 1, [f.render() for f in hits]
        f = hits[0]
        assert f.qualname == "Shared.racy_read"
        # the chain crosses the module boundary: spawned in s.py,
        # runs w.run -> Shared.bump, shares the instance with
        # racy_read
        assert "w:run" in f.message
        assert "w:Shared.bump" in f.message
        assert "shares" in f.message and "instance state" in f.message

    def test_lock_guards_declaration_is_authoritative(self, tmp_path):
        # the vote alone would NOT guard `state` (held at 1 of 3
        # accesses) — the class-body declaration overrides it
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "class S:\n"
            "    _lock_guards = (\"state\",)\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.state = \"idle\"\n"
            "    def start(self):\n"
            "        threading.Thread(target=self.run).start()\n"
            "    def run(self):\n"
            "        with self._lock:\n"
            "            self.state = \"running\"\n"
            "    def status(self):\n"
            "        return self.state\n"
            "    def reset(self):\n"
            "        self.state = \"idle\"\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H17")
        # the read in status() fires on the declaration's authority;
        # the plain WRITE in reset() is H3's beat — H17 skips it so
        # one decision never needs two suppressions
        assert len(hits) == 1, [f.render() for f in hits]
        assert hits[0].qualname == "S.status"
        assert "declared by `_lock_guards`" in hits[0].message
        assert all(h.qualname != "S.reset" for h in hits)


# ---------------------------------------------------------------------------
# H18 — unsafe publication of mutable state


class TestH18:
    def test_argument_handoff_mutated_both_sides(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "def worker(buf):\n"
            "    buf.append(1)\n"
            "\n"
            "def main():\n"
            "    buf = []\n"
            "    t = threading.Thread(target=worker, args=(buf,))\n"
            "    t.start()\n"
            "    buf.append(2)\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H18")
        assert len(hits) == 1, [f.render() for f in hits]
        f = hits[0]
        assert f.qualname == "main"
        assert "mutable local `buf`" in f.message
        assert "a thread target" in f.message
        assert "m:worker" in f.message
        assert "`buf` parameter" in f.message

    def test_closure_capture_mutated_both_sides(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "def main():\n"
            "    buf = []\n"
            "    def worker():\n"
            "        buf.append(1)\n"
            "    t = threading.Thread(target=worker)\n"
            "    t.start()\n"
            "    buf.append(2)\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H18")
        assert len(hits) == 1, [f.render() for f in hits]
        assert "captured by" in hits[0].message

    def test_common_lock_on_both_sides_is_silent(self, tmp_path):
        # the SAME lexical lock seen from the spawner and from the
        # nested target carries two function-scoped ids but one name
        # — the token comparison must recognize it as common
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "def main():\n"
            "    lock = threading.Lock()\n"
            "    buf = []\n"
            "    def worker():\n"
            "        with lock:\n"
            "            buf.append(1)\n"
            "    t = threading.Thread(target=worker)\n"
            "    t.start()\n"
            "    with lock:\n"
            "        buf.append(2)\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H18") == []

    def test_handoff_without_spawner_mutation_is_silent(
            self, tmp_path):
        # publishing and then never touching it again is the
        # immutable-snapshot discipline — no finding
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "def worker(buf):\n"
            "    buf.append(1)\n"
            "\n"
            "def main():\n"
            "    buf = []\n"
            "    buf.append(0)\n"
            "    t = threading.Thread(target=worker, args=(buf,))\n"
            "    t.start()\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H18") == []

    def test_inline_suppression_downgrades(self, tmp_path):
        root = _tree(tmp_path, {"m.py": (
            "import threading\n"
            "\n"
            "def worker(buf):\n"
            "    buf.append(1)\n"
            "\n"
            "def main():\n"
            "    buf = []\n"
            "    t = threading.Thread(target=worker, args=(buf,))  "
            "# sparkdl-lint: allow[H18] -- join() below serializes\n"
            "    t.start()\n"
            "    t.join()\n"
            "    buf.append(2)\n")})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H18") == []
        sup = _sup(found, "H18")
        assert len(sup) == 1
        assert "join() below serializes" in sup[0].suppression


# ---------------------------------------------------------------------------
# H19 — atomicity split (check-then-act across separate holds)


H19_SPLIT = (
    "import threading\n"
    "\n"
    "class Q:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self.rows = []\n"
    "        self.cap = 4\n"
    "\n"
    "    def start(self):\n"
    "        threading.Thread(target=self.drain).start()\n"
    "\n"
    "    def drain(self):\n"
    "        with self._lock:\n"
    "            if self.rows:\n"
    "                self.rows.pop()\n"
    "\n"
    "    def offer(self, row):\n"
    "        with self._lock:\n"
    "            if len(self.rows) >= self.cap:\n"
    "                return False\n"
    "        with self._lock:\n"
    "            self.rows.append(row)\n"
    "        return True\n")


class TestH19:
    def test_split_check_then_act_fires(self, tmp_path):
        root = _tree(tmp_path, {"m.py": H19_SPLIT})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        hits = _unsup(found, "H19")
        assert len(hits) == 1, [f.render() for f in hits]
        f = hits[0]
        assert f.qualname == "Q.offer"
        assert "check-then-act split on `self.rows`" in f.message
        assert "Q._lock at line 19" in f.message
        assert "SEPARATE hold at line 22" in f.message
        assert "TOCTOU" in f.message
        assert "the main thread" in f.message

    def test_single_hold_is_atomic_and_silent(self, tmp_path):
        src = H19_SPLIT.replace(
            "    def offer(self, row):\n"
            "        with self._lock:\n"
            "            if len(self.rows) >= self.cap:\n"
            "                return False\n"
            "        with self._lock:\n"
            "            self.rows.append(row)\n",
            "    def offer(self, row):\n"
            "        with self._lock:\n"
            "            if len(self.rows) >= self.cap:\n"
            "                return False\n"
            "            self.rows.append(row)\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H19") == []

    def test_double_checked_locking_is_the_remedy_not_the_hazard(
            self, tmp_path):
        src = H19_SPLIT.replace(
            "        with self._lock:\n"
            "            self.rows.append(row)\n",
            "        with self._lock:\n"
            "            if len(self.rows) < self.cap:\n"
            "                self.rows.append(row)\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H19") == []

    def test_inline_suppression_downgrades(self, tmp_path):
        src = H19_SPLIT.replace(
            "            self.rows.append(row)\n",
            "            self.rows.append(row)  "
            "# sparkdl-lint: allow[H19] -- overshoot by one row is "
            "acceptable here\n")
        root = _tree(tmp_path, {"m.py": src})
        found = analyze_paths([root], rules=RACE_RULES,
                              cache_path=None)
        assert _unsup(found, "H19") == []
        sup = _sup(found, "H19")
        assert len(sup) == 1
        assert "overshoot by one row" in sup[0].suppression


# ---------------------------------------------------------------------------
# the real package: thread-root inventory + guarded-by pins


class TestRealPackageTopology:
    def test_known_concurrent_loops_are_roots(self):
        topo = thread_topology(_package_graph())
        roots = set(topo.roots)
        assert ("sparkdl_tpu.serve.server::"
                "ModelSession._serve_loop") in roots
        assert ("sparkdl_tpu.obs.watchdog::"
                "StallWatchdog._monitor") in roots
        assert ("sparkdl_tpu.autotune.core::"
                "AutotuneController.step") in roots
        # the pipeline worker pool + the flight recorder's signal
        # handler arrive via spawn-site detection, not the table
        assert ("sparkdl_tpu.data.pipeline::"
                "_pooled_partition_task") in roots
        assert ("sparkdl_tpu.obs.flight::"
                "FlightRecorder._install_signal._on_sigusr2") in roots

    def test_autotune_apply_path_is_multi_worker(self):
        topo = thread_topology(_package_graph())
        root = topo.roots[
            "sparkdl_tpu.autotune.core::AutotuneController.step"]
        assert root.multi

    def test_hot_structures_are_concurrent(self):
        topo = thread_topology(_package_graph())
        for key in (
                "sparkdl_tpu.serve.batching::RequestQueue.offer",
                "sparkdl_tpu.serve.batching::RequestQueue.collect",
                "sparkdl_tpu.obs.watchdog::StallWatchdog.pulse",
                "sparkdl_tpu.obs.registry::Reservoir.observe",
                "sparkdl_tpu.data.pipeline::"
                "HostPipeline._retire_locked"):
            assert topo.is_concurrent(key), key

    def test_single_threaded_helpers_stay_out(self):
        # the analyzer's own code and the jit-cache accessor run on
        # whatever single thread calls them — no spawn root reaches
        # them, so the race rules must leave them alone
        topo = thread_topology(_package_graph())
        for key in (
                "sparkdl_tpu.analysis.suppress::"
                "SuppressionIndex.lookup",
                "sparkdl_tpu.graph.function::ModelFunction.jitted"):
            assert not topo.is_concurrent(key), key

    def test_request_queue_guards_are_declared(self):
        model = _guard_model(_package_graph())
        gi = model.guards.get(
            ("sparkdl_tpu.serve.batching::RequestQueue", "rows"))
        assert gi is not None and gi.declared
        assert gi.lock == \
            "sparkdl_tpu.serve.batching::RequestQueue._lock"


# ---------------------------------------------------------------------------
# the sweep's fixes + the acceptance gate


class TestRealPackageClean:
    def test_package_tools_examples_clean_under_race_rules(self):
        targets = [PKG_DIR]
        for extra in ("tools", "examples"):
            d = os.path.join(REPO_ROOT, extra)
            if os.path.isdir(d):
                targets.append(d)
        found = analyze_paths(targets, rules=RACE_RULES,
                              cache_path=None)
        unsup = [f for f in found if not f.suppressed]
        assert unsup == [], "\n".join(f.render() for f in unsup)

    def test_server_close_reads_worker_under_lock(self):
        """Regression pin for the sweep's serve fix: close() must
        read the dispatcher handle under the session lock (a racing
        submit() may be swapping a fresh worker in)."""
        with open(os.path.join(PKG_DIR, "serve", "server.py")) as f:
            src = f.read()
        assert "with self._lock:\n            worker = self._worker" \
            in src

    def test_ledger_verdict_reads_ceilings_under_lock(self):
        with open(os.path.join(PKG_DIR, "obs", "ledger.py")) as f:
            src = f.read()
        assert "with self._lock:\n" \
               "            ceilings = self._ceilings or {}" in src

    def test_policy_state_code_reads_under_lock(self):
        with open(os.path.join(PKG_DIR, "resilience",
                               "policy.py")) as f:
            src = f.read()
        assert "with self._lock:\n" \
               "            return _STATE_CODES[self.state]" in src


# ---------------------------------------------------------------------------
# serialization: the facts ride the cache (ANALYZER_VERSION 8)


class TestRaceFactsCache:
    def test_analyzer_version_is_eight(self):
        """The thread/race facts changed the ModuleFacts schema; v8
        is what forces every v7 cache entry cold. A future schema
        change must bump again — update this pin when it does."""
        assert cache_mod.ANALYZER_VERSION == 8

    def test_race_findings_survive_the_cache_round_trip(
            self, tmp_path):
        root = _tree(tmp_path, {"m.py": H17_RACY,
                                "q.py": H19_SPLIT})
        cache = str(tmp_path / "cache.json")
        cold = analyze_paths([root], rules=RACE_RULES,
                             cache_path=cache)
        stats: dict = {}
        warm = analyze_paths([root], rules=RACE_RULES,
                             cache_path=cache, cache_stats=stats)
        assert stats["hits"] == 2 and stats["misses"] == 0
        assert [f.render() for f in cold] == \
            [f.render() for f in warm]
        assert _unsup(warm, "H17") and _unsup(warm, "H19")

    def test_all_rules_has_nineteen_entries(self):
        assert len(ALL_RULES) == 19
        assert {"H17", "H18", "H19"} <= set(ALL_RULES)


# ---------------------------------------------------------------------------
# the runtime cross-check: assert_lock_owned under SPARKDL_TPU_SANITIZE


class TestAssertLockOwned:
    def test_noop_when_sanitize_is_off(self, monkeypatch):
        import threading
        from sparkdl_tpu.runtime.sanitize import assert_lock_owned
        monkeypatch.delenv("SPARKDL_TPU_SANITIZE", raising=False)
        assert_lock_owned(threading.Lock(), "x")     # held or not
        assert_lock_owned(None, "x")                 # even None

    def test_armed_raises_on_unheld_and_none(self, monkeypatch):
        import threading
        from sparkdl_tpu.runtime.sanitize import assert_lock_owned
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        lock = threading.Lock()
        with pytest.raises(AssertionError, match="caller-holds"):
            assert_lock_owned(lock, "helper")
        with pytest.raises(AssertionError, match="no guard"):
            assert_lock_owned(None, "helper")
        with lock:
            assert_lock_owned(lock, "helper")        # held: fine
        rlock = threading.RLock()
        with pytest.raises(AssertionError):
            assert_lock_owned(rlock, "helper")
        with rlock:
            assert_lock_owned(rlock, "helper")

    def test_serve_queue_helpers_assert_their_contract(
            self, monkeypatch):
        from sparkdl_tpu.serve.batching import RequestQueue
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        q = RequestQueue()
        with pytest.raises(AssertionError):
            q._max_queued_priority()
        with pytest.raises(AssertionError):
            q._pick_victims(priority=1, overflow=1)
        with q._lock:
            assert q._max_queued_priority() == -1
            assert q._pick_victims(priority=1, overflow=0) == []

    def test_pool_registry_retire_asserts(self, monkeypatch):
        from sparkdl_tpu.data.pipeline import HostPipeline
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        p = HostPipeline(mode="thread")
        with pytest.raises(AssertionError):
            p._retire_locked(None)
        with p._lock:
            assert p._retire_locked(None) is None

    def test_violations_are_counted(self, monkeypatch):
        import threading
        from sparkdl_tpu.obs import default_registry
        from sparkdl_tpu.runtime.sanitize import assert_lock_owned
        monkeypatch.setenv("SPARKDL_TPU_SANITIZE", "1")
        before = default_registry().counter(
            "sanitize.lock_violations").value
        with pytest.raises(AssertionError):
            assert_lock_owned(threading.Lock(), "counted")
        after = default_registry().counter(
            "sanitize.lock_violations").value
        assert after == before + 1
