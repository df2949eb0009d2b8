"""BatchRunner tests (L1: static-shape chunking, padding, async gather)."""

import numpy as np
import pytest

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.runtime.runner import BatchRunner, RunnerMetrics


def _double_fn():
    return ModelFunction.fromSingle(lambda x: x * 2.0, None,
                                    input_shape=(3,))


class TestBatchRunner:
    def test_exact_multiple(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        out = r.run({"input": x})["output"]
        np.testing.assert_allclose(out, x * 2)

    def test_padding_last_chunk(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(21, dtype=np.float32).reshape(7, 3)
        out = r.run({"input": x})["output"]
        assert out.shape == (7, 3)
        np.testing.assert_allclose(out, x * 2)

    def test_smaller_than_batch(self):
        r = BatchRunner(_double_fn(), batch_size=64)
        x = np.ones((2, 3), np.float32)
        np.testing.assert_allclose(r.run({"input": x})["output"], 2.0)

    def test_empty_input(self):
        r = BatchRunner(_double_fn(), batch_size=4)
        out = r.run({"input": np.zeros((0, 3), np.float32)})
        assert out["output"].shape == (0, 3)

    def test_metrics(self):
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        r.run({"input": np.zeros((10, 3), np.float32)})
        assert m.rows == 10
        assert m.batches == 3
        assert m.seconds > 0
        assert m.rows_per_second > 0

    def test_row_count_mismatch(self):
        def two_in(params, inputs):
            return {"out": inputs["a"] + inputs["b"]}
        mf = ModelFunction(two_in, None,
                           {"a": ((2,), np.float32),
                            "b": ((2,), np.float32)})
        r = BatchRunner(mf, batch_size=4)
        with pytest.raises(ValueError, match="rows"):
            r.run({"a": np.zeros((3, 2), np.float32),
                   "b": np.zeros((4, 2), np.float32)})

    def test_signature_validation_names_both_sides(self):
        """A missing/mis-shaped input raises HERE with both names —
        not a bare KeyError or a flax shape error from inside the
        traced program (review r5 probe)."""
        r = BatchRunner(_double_fn(), batch_size=4)
        with pytest.raises(ValueError, match="missing from"):
            r.run({"wrong": np.zeros((4, 3), np.float32)})
        with pytest.raises(ValueError, match="expects"):
            r.run({"input": np.zeros((4, 7), np.float32)})
        # extra keys are tolerated (the model ignores them)
        out = r.run({"input": np.ones((2, 3), np.float32),
                     "extra": np.zeros((2, 1), np.float32)})
        np.testing.assert_allclose(out["output"], 2.0)
        # zero-row inputs keep their empty-batch tolerance even when
        # FLAT (empty variable-list columns arrive as (0,))
        empty = r.run({"input": np.zeros((0,), np.float32)})
        assert empty["output"].shape == (0, 3)
        # jax models with scalar rows () ARE enforced ((4,3) into a
        # scalar-input model must not sail into an XLA error)
        scal = BatchRunner(ModelFunction.fromSingle(
            lambda x: x * 2.0, None, input_shape=()), batch_size=4)
        with pytest.raises(ValueError, match="expects"):
            scal.run({"input": np.zeros((4, 3), np.float32)})

    def test_deserialize_garbage_raises_clearly(self):
        from sparkdl_tpu.graph.ingest import ModelIngest

        with pytest.raises(ValueError, match="StableHLO"):
            ModelIngest.fromExport(b"definitely not an export")

    def test_bad_batch_size(self):
        with pytest.raises(ValueError):
            BatchRunner(_double_fn(), batch_size=0)

    def test_max_inflight_resolution(self):
        from sparkdl_tpu.runtime.runner import (
            MAX_INFLIGHT_BATCHES,
            resolve_max_inflight,
        )

        # the default: double-buffered
        assert resolve_max_inflight(None) == MAX_INFLIGHT_BATCHES == 2
        # an explicit depth is kept; 0 is the zero-length queue
        assert resolve_max_inflight(5) == 5
        assert resolve_max_inflight(0) == 0
        # a negative depth is loud
        with pytest.raises(ValueError, match="max_inflight"):
            resolve_max_inflight(-1)
        with pytest.raises(ValueError, match="max_inflight"):
            BatchRunner(_double_fn(), max_inflight=-1)
        assert BatchRunner(_double_fn(), max_inflight=0).max_inflight == 0
        assert BatchRunner(_double_fn()).max_inflight == 2
        # the removed knobs are gone, not aliased
        with pytest.raises(TypeError):
            BatchRunner(_double_fn(), strategy="deferred")

    def test_all_depths_produce_identical_outputs(self):
        """The window's depth is a pure dispatch policy — same
        results, same order, for aligned, tail-padded, and N=0 inputs
        (the slab-output parity pin)."""
        cases = {
            "tail": np.arange(22 * 3, dtype=np.float32).reshape(22, 3),
            "aligned": np.arange(8 * 3, dtype=np.float32).reshape(8, 3),
            "empty": np.zeros((0, 3), np.float32),
        }
        for name, x in cases.items():
            expected = None
            for depth in (0, 1, 2, 8):
                r = BatchRunner(_double_fn(), batch_size=4,
                                max_inflight=depth)
                out = r.run({"input": x})["output"]
                assert out.shape == x.shape, (name, depth)
                if expected is None:
                    expected = out
                else:
                    np.testing.assert_array_equal(out, expected)
            np.testing.assert_allclose(expected, x * 2.0)

    def test_host_backend(self):
        def host_apply(params, inputs):
            return {"y": np.asarray(inputs["x"]) + 1.0}
        mf = ModelFunction(host_apply, None, {"x": ((3,), np.float32)},
                           output_names=["y"], backend="host")
        r = BatchRunner(mf, batch_size=4)
        x = np.zeros((6, 3), np.float32)
        np.testing.assert_allclose(r.run({"x": x})["y"], 1.0)

    def test_device_params_cached_and_invalidated(self):
        """Params transfer to the device once per params object and the
        cache invalidates when .params is reassigned (regression: a
        runner-level cache served stale weights after reassignment)."""
        mf = ModelFunction.fromSingle(
            lambda p, x: x * p["scale"], {"scale": np.float32(2.0)},
            input_shape=(2,))
        r = BatchRunner(mf, batch_size=4)
        x = np.ones((3, 2), np.float32)
        np.testing.assert_allclose(r.run({"input": x})["output"], 2.0)
        assert mf.device_params() is mf.device_params()  # cached

        mf.params = {"scale": np.float32(5.0)}
        np.testing.assert_allclose(r.run({"input": x})["output"], 5.0)

    def test_aligned_run_is_zero_copy(self):
        """The zero-copy hot path pinned by counters: a batch-aligned
        contiguous input ships as plain views — RunnerMetrics reports
        ZERO bytes staged and ZERO bytes copied. The input is marked
        read-only so any staging write into it would raise."""
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        x = np.arange(24, dtype=np.float32).reshape(8, 3)
        x.setflags(write=False)
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)
        assert m.bytes_staged == 0 and m.bytes_copied == 0, m
        # a tail-padded run stages EXACTLY the tail rows, nothing more
        y = np.arange(30, dtype=np.float32).reshape(10, 3)
        y.setflags(write=False)
        np.testing.assert_allclose(r.run({"input": y})["output"], y * 2)
        assert m.bytes_staged == y[8:].nbytes, m
        assert m.bytes_copied == 0, m

    def test_non_contiguous_input_counts_copies(self):
        """Non-contiguous rows (e.g. a strided column view) can't ship
        as views — they are copied, and the copy is COUNTED: the
        counters must not claim zero-copy for a path that copies."""
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), batch_size=4, metrics=m)
        x = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)[:, ::2]
        assert not x.flags.c_contiguous
        np.testing.assert_allclose(r.run({"input": x})["output"], x * 2)
        assert m.bytes_copied == x.nbytes, m
        assert m.bytes_staged == 0, m

    def test_iter_padded_chunks_views_and_persistent_staging(self):
        """Full chunks are VIEWS of the input (zero host copies); the
        tail stages through ONE persistent buffer reused across calls,
        with the pad region re-zeroed when the next tail is shorter."""
        from sparkdl_tpu.runtime.runner import (
            CopyCounters,
            PadStaging,
            iter_padded_chunks,
        )

        x = np.arange(33, dtype=np.float32).reshape(11, 3)
        x.setflags(write=False)
        staging, counters = PadStaging(), CopyCounters()
        chunks = list(iter_padded_chunks({"x": x}, 11, 4,
                                         staging, counters))
        assert [v for v, _ in chunks] == [4, 4, 3]
        assert np.shares_memory(chunks[0][1]["x"], x)
        assert np.shares_memory(chunks[1][1]["x"], x)
        tail = chunks[2][1]["x"]
        assert not np.shares_memory(tail, x)
        assert tail.shape == (4, 3)
        np.testing.assert_array_equal(tail[:3], x[8:])
        np.testing.assert_array_equal(tail[3:], 0.0)
        assert counters.bytes_copied == 0
        assert counters.bytes_staged == x[8:].nbytes
        # second call, shorter tail: SAME buffer object, stale rows
        # from the previous tail re-zeroed
        y = np.ones((6, 3), np.float32)
        c2 = list(iter_padded_chunks({"x": y}, 6, 4, staging,
                                     CopyCounters()))
        assert c2[1][1]["x"] is tail  # persistent buffer reused
        np.testing.assert_array_equal(tail[:2], 1.0)
        np.testing.assert_array_equal(tail[2:], 0.0)

    def test_runner_pickles_without_lock_state(self):
        """Device stage closures holding a runner ship to Spark
        executors — the staging lock/buffers must drop on pickle and
        come back fresh (the RunnerMetrics discipline)."""
        cloudpickle = pytest.importorskip("cloudpickle")

        r = BatchRunner(_double_fn(), batch_size=4)
        x = np.arange(30, dtype=np.float32).reshape(10, 3)
        r.run({"input": x})  # warm staging so there IS state to drop
        r2 = cloudpickle.loads(cloudpickle.dumps(r))
        np.testing.assert_allclose(r2.run({"input": x})["output"],
                                   x * 2.0)

    def test_params_cache_purges_all_placements(self):
        """Reassigning .params purges every cached placement, not just
        the next-accessed key (regression: dead replicated copies held
        device memory)."""
        from sparkdl_tpu.parallel.mesh import make_mesh
        mf = ModelFunction.fromSingle(
            lambda p, x: x * p["s"], {"s": np.float32(2.0)},
            input_shape=(2,))
        mesh = make_mesh()
        mf.device_params()
        mf.replicated_params(mesh)
        assert len(mf._params_cache) == 2
        mf.params = {"s": np.float32(3.0)}
        mf.device_params()   # triggers purge of the stale replicated copy
        assert len(mf._params_cache) == 1
        np.testing.assert_allclose(
            np.asarray(mf.replicated_params(mesh)["s"]), 3.0)


def _span_letters(records):
    """The order of dispatches (``d``; ``n`` where the rows are the
    next run's), readbacks (``g``) and run ends (``|``)."""
    letters = {"device_get": "g", "runner.run": "|",
               "runner.run_sharded": "|"}
    out = []
    for s in records:
        if s.name == "dispatch":
            out.append("n" if s.attrs.get("whose") == "next" else "d")
        elif s.name in letters:
            out.append(letters[s.name])
    return "".join(out)


@pytest.fixture
def ship_counters():
    """Deltas of the registry's carry counters since the test began."""
    from sparkdl_tpu.obs import default_registry
    names = ("ship.boundary_carried", "ship.boundary_cold",
             "ship.carry_dropped")
    before = default_registry().snapshot()

    def delta():
        now = default_registry().snapshot()
        return tuple(int(now.get(k, 0) - before.get(k, 0))
                     for k in names)
    return delta


@pytest.fixture
def armed_spans():
    from sparkdl_tpu.obs import tracer
    tr = tracer()
    tr.arm()
    tr.clear()
    yield tr
    tr.arm_from_env()
    tr.clear()


class TestBoundaryCarry:
    """``run(inputs, upcoming=...)``: the in-flight window outlives the
    call (runtime/runner.py::BoundaryCarry). Counts and orders of
    events, never speeds."""

    @staticmethod
    def _parts(sizes, seed=0):
        rng = np.random.default_rng(seed)
        return [{"input": rng.normal(size=(n, 3)).astype(np.float32)}
                for n in sizes]

    @staticmethod
    def _chain(runner, parts, announce=True):
        return [runner.run(p, upcoming=(parts[i + 1] if announce
                                        and i + 1 < len(parts) else None))
                for i, p in enumerate(parts)]

    # a multiple of the batch, not a multiple, shorter than the window
    # (one chunk, fewer chunks than max_inflight), empty
    @pytest.mark.parametrize("sizes", [
        [16, 16, 16], [14, 9, 21], [4, 4, 4, 4], [3, 2, 1], [8, 3, 16],
        [16, 0, 16], [0, 5], [16]])
    @pytest.mark.parametrize("max_inflight", [0, 1, 2, 3])
    def test_rows_equal_with_and_without_the_hand_off(self, sizes,
                                                      max_inflight):
        parts = self._parts(sizes)
        cold = self._chain(BatchRunner(_double_fn(), 4,
                                       max_inflight=max_inflight),
                           parts, announce=False)
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), 4, max_inflight=max_inflight,
                        metrics=m)
        warm = self._chain(r, parts)
        for p, a, b in zip(parts, cold, warm):
            np.testing.assert_array_equal(a["output"], b["output"])
            np.testing.assert_allclose(b["output"], p["input"] * 2.0)
        assert m.rows == sum(sizes)
        assert m.batches == sum(-(-n // 4) for n in sizes)
        assert r._carry.in_flight == 0
        device_runs = sum(1 for n in sizes if n)
        assert m.boundary_carried + m.boundary_cold == \
            max(0, device_runs - 1)
        if max_inflight == 0:
            assert m.boundary_carried == 0   # nothing in flight to carry under

    @pytest.mark.parametrize("sizes", [[16, 16, 16], [14, 9, 21], [8, 3, 16]])
    @pytest.mark.parametrize("max_inflight", [0, 1, 2, 3])
    def test_never_more_than_the_window_and_one_in_flight(
            self, sizes, max_inflight):
        """Inside a run and across the hand-off alike: one batch goes
        in for each that comes out (``ship.inflight_peak``)."""
        from sparkdl_tpu.obs import default_registry
        peak = default_registry().gauge("ship.inflight_peak")
        peak.set(0)
        r = BatchRunner(_double_fn(), 4, max_inflight=max_inflight)
        self._chain(r, self._parts(sizes))
        most = max(-(-n // 4) for n in sizes)
        assert peak.value == min(most, max_inflight + 1)
        assert default_registry().gauge("ship.inflight").value == 0

    def test_the_next_runs_first_chunks_launch_under_this_ones_last(
            self, armed_spans, ship_counters):
        r = BatchRunner(_double_fn(), 4)        # 2 in flight
        parts = self._parts([16, 16, 16])
        self._chain(r, parts)
        # inside a run the window is one in for one out; at its end
        # two chunks of the NEXT run go in as the last two come out,
        # and that run starts with them at the head of its queue
        assert _span_letters(armed_spans.spans()) == \
            "dddgdgngng|dgdgngng|dgdggg|"
        spans = armed_spans.spans()
        runs = [s for s in spans if s.name == "runner.run"]
        for before, after in zip(runs, runs[1:]):
            carried = [s for s in spans if s.name == "dispatch"
                       and s.attrs.get("whose") == "next"
                       and s.parent_id == before.span_id]
            last_get = max(s.end for s in spans
                           if s.name == "device_get"
                           and s.parent_id == before.span_id)
            assert len(carried) == 2
            assert min(s.start for s in carried) < last_get
            assert all(s.end <= after.start for s in carried)
        assert ship_counters() == (2, 0, 0)
        assert (r.metrics.boundary_carried, r.metrics.boundary_cold) \
            == (2, 0)

    @pytest.mark.parametrize("max_inflight,letters", [
        (0, "dgdgdgdg|dgdg|"),
        (2, "dddgdggg|ddgg|")])
    def test_without_upcoming_the_spans_are_the_parents(
            self, max_inflight, letters, armed_spans, ship_counters):
        """The path ModelServer, the UDF registry and every direct
        caller stay on: the sequences are those the code made before
        the carry existed (recorded from commit b5a10c7)."""
        r = BatchRunner(_double_fn(), 4, max_inflight=max_inflight)
        x = np.arange(42, dtype=np.float32).reshape(14, 3)
        r.run({"input": x})
        armed_spans.clear()
        r.run({"input": x})
        r.run({"input": x[:5]})
        assert _span_letters(armed_spans.spans()) == letters
        assert all("whose" not in s.attrs for s in armed_spans.spans())
        assert r._carry.in_flight == 0
        assert ship_counters() == (0, 2, 0)

    def test_upcoming_may_be_a_callable_asked_once_and_late(
            self, armed_spans):
        r = BatchRunner(_double_fn(), 4)
        a, b = self._parts([16, 8])
        asked = []

        def look():
            asked.append(_span_letters(armed_spans.spans()))
            return b
        r.run(a, upcoming=look)
        # asked once, when every chunk of ``a`` had been dispatched
        assert asked == ["dddgdg"]
        out = r.run(b, upcoming=lambda: None)
        np.testing.assert_allclose(out["output"], b["input"] * 2.0)
        assert r.metrics.boundary_carried == 1

    def test_other_inputs_or_another_batch_size_drop_the_carry(
            self, ship_counters):
        a, b, c = self._parts([16, 16, 16])
        r = BatchRunner(_double_fn(), 4)
        r.run(a, upcoming=b)
        assert r._carry.in_flight == 2
        out = r.run(c)                  # not the inputs announced
        np.testing.assert_allclose(out["output"], c["input"] * 2.0)
        assert ship_counters() == (0, 1, 1)
        r.run(a, upcoming=b)
        r.batch_size = 8                # the controller moved it
        out = r.run(b)
        np.testing.assert_allclose(out["output"], b["input"] * 2.0)
        assert ship_counters() == (0, 3, 2)
        assert r._carry.in_flight == 0
        # equal values in other memory are other inputs
        r.batch_size = 4
        r.run(a, upcoming=b)
        twin = {"input": b["input"].copy()}
        np.testing.assert_allclose(r.run(twin)["output"],
                                   b["input"] * 2.0)
        assert ship_counters() == (0, 5, 3)
        # the same memory under new array objects is the same inputs
        r.run(a, upcoming=b)
        again = {"input": b["input"][:]}
        np.testing.assert_allclose(r.run(again)["output"],
                                   b["input"] * 2.0)
        assert ship_counters() == (1, 6, 3)

    def test_an_exception_drops_the_carry_and_the_retry_runs_cold(
            self, monkeypatch, ship_counters):
        from sparkdl_tpu.runtime import runner as rmod
        a, b = self._parts([16, 16])
        r = BatchRunner(_double_fn(), 4)
        drains = []

        def fail_third_drain(site):
            if site == "ship.drain":
                drains.append(site)
                if len(drains) == 3:    # the first with a carry aloft
                    raise OSError("injected")
        monkeypatch.setattr(rmod, "maybe_fail", fail_third_drain)
        with pytest.raises(OSError, match="injected"):
            r.run(a, upcoming=b)
        assert r._carry.in_flight == 0
        assert ship_counters() == (0, 0, 1)
        out_a = r.run(a, upcoming=b)    # re-dispatches from its inputs
        out_b = r.run(b)
        np.testing.assert_allclose(out_a["output"], a["input"] * 2.0)
        np.testing.assert_allclose(out_b["output"], b["input"] * 2.0)
        assert ship_counters() == (1, 1, 1)
        assert not r._staging_lock.locked()

    def test_drop_carry_leaves_nothing_pending(self, ship_counters):
        from sparkdl_tpu.obs import default_registry
        a, b = self._parts([16, 16])
        r = BatchRunner(_double_fn(), 4)
        r.run(a, upcoming=b)
        assert r._carry.in_flight == 2
        r.drop_carry()
        assert r._carry.in_flight == 0
        assert default_registry().snapshot()["ship.inflight"] == 0
        r.drop_carry()                  # nothing to drop: not counted
        assert ship_counters() == (0, 0, 1)
        np.testing.assert_allclose(r.run(b)["output"],
                                   b["input"] * 2.0)

    def test_a_carry_is_its_threads_alone(self, ship_counters):
        """Another thread's run() between two runs of a chain leaves
        the carry where it is and runs cold."""
        import threading
        a, b, c = self._parts([16, 16, 16])
        r = BatchRunner(_double_fn(), 4)
        r.run(a, upcoming=b)
        got = {}
        th = threading.Thread(
            target=lambda: got.update(r.run(c, upcoming=a)))
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
        np.testing.assert_allclose(got["output"], c["input"] * 2.0)
        assert r._carry.in_flight == 2  # untouched, and not replaced
        np.testing.assert_allclose(r.run(b)["output"],
                                   b["input"] * 2.0)
        assert ship_counters() == (1, 1, 0)

    def test_a_run_that_overlaps_another_bypasses_the_carry(
            self, ship_counters):
        """The staging try-lock lost (another run() holds it): the run
        is cold and announces nothing; its own carry, launched for this
        very run, is dropped, not left behind."""
        a, b, c = self._parts([16, 16, 16])
        r = BatchRunner(_double_fn(), 4)
        r.run(a, upcoming=b)
        assert r._staging_lock.acquire(blocking=False)
        try:
            out = r.run(b, upcoming=c)
        finally:
            r._staging_lock.release()
        np.testing.assert_allclose(out["output"], b["input"] * 2.0)
        assert r._carry.in_flight == 0
        assert ship_counters() == (0, 1, 1)

    def test_threads_through_one_runner_with_the_hand_off_on(self):
        """Overlapping chains on ONE runner: right rows in every
        thread; a run that overlaps another, or finds another thread's
        carry, bypasses the carry and counts cold."""
        import sys
        import threading
        m = RunnerMetrics()
        r = BatchRunner(_double_fn(), 4, metrics=m)
        chains = [self._parts([16, 9, 16, 4, 12], seed=i)
                  for i in range(6)]
        outs, errors = {}, []
        gate = threading.Barrier(len(chains))

        def work(i):
            try:
                gate.wait(timeout=30)
                outs[i] = self._chain(r, chains[i])
            except Exception as e:  # pragma: no cover - reporting
                errors.append(e)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(len(chains))]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not errors and not any(th.is_alive() for th in threads)
        for i, parts in enumerate(chains):
            for p, o in zip(parts, outs[i]):
                np.testing.assert_allclose(o["output"],
                                           p["input"] * 2.0)
        runs = sum(len(c) for c in chains)
        assert m.rows == sum(len(p["input"]) for c in chains for p in c)
        assert m.boundary_carried + m.boundary_cold == runs - 1
        assert m.boundary_cold >= len(chains) - 1
        assert r._carry.in_flight == 0
        assert not r._staging_lock.locked()

    def test_carried_tail_never_shares_the_persistent_stager(self):
        """A padded tail dispatched for the NEXT run is staged in a
        buffer of its own: the runner's persistent one may still be
        read by this run's own tail, in flight beside it."""
        r = BatchRunner(_double_fn(), 4)
        a, b = self._parts([3, 2])      # both are all tail
        out_a = r.run(a, upcoming=b)
        out_b = r.run(b)
        np.testing.assert_allclose(out_a["output"], a["input"] * 2.0)
        np.testing.assert_allclose(out_b["output"], b["input"] * 2.0)
        assert r.metrics.boundary_carried == 1

    def test_a_shipped_runner_arrives_with_an_empty_window(self):
        cloudpickle = pytest.importorskip("cloudpickle")
        a, b = self._parts([16, 16])
        r = BatchRunner(_double_fn(), 4)
        r.run(a, upcoming=b)
        r2 = cloudpickle.loads(cloudpickle.dumps(r))
        assert r2._carry.in_flight == 0
        np.testing.assert_allclose(r2.run(b)["output"],
                                   b["input"] * 2.0)
        r.drop_carry()
