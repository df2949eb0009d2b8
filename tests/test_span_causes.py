"""Spans with causes, on the profiler's clock (obs/trace.py and what
records into it).

Pinned here: every span carries its own id and the id of the span open
on its thread when it began, per thread; the post-hoc recorders
(``timed_device_get``, the compile log's ``compile`` span) take the same
parent; ``runner.run``/``runner.run_sharded`` time the interval
``RunnerMetrics.seconds`` times; ``TensorTransformer`` splits the
hand-off between two runs into spans of its own; the compiled program
is named after the model; an armed compile event keeps the program's
instruction-to-scope map; ``utils.profiling.trace`` takes a device
trace with the host tracer off and the program's spans beside it; and
a disarmed transform records nothing at all."""

import json
import threading

import jax
import numpy as np
import pytest

from sparkdl_tpu.data.frame import DataFrame
from sparkdl_tpu.data.tensors import append_tensor_column
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.models.zoo import getModelFunction
from sparkdl_tpu.obs import Tracer, compile_log, tracer
from sparkdl_tpu.obs.compile_log import CompileLog, instruction_scopes
from sparkdl_tpu.obs.trace import timed_device_get
from sparkdl_tpu.parallel.inference import ShardedBatchRunner
from sparkdl_tpu.parallel.mesh import make_mesh
from sparkdl_tpu.runtime.runner import BatchRunner
from sparkdl_tpu.transformers.tensor_transform import TensorTransformer
from sparkdl_tpu.utils import profiling


@pytest.fixture()
def armed():
    """The process-wide tracer and compile log, armed and empty, put
    back to following the environment afterwards."""
    t, log = tracer(), compile_log()
    t.clear()
    log.clear()
    t.arm()
    log.arm()
    yield t
    t.arm_from_env()
    log.arm_from_env()
    t.clear()
    log.clear()


def _by_name(spans):
    return {r.name: r for r in spans}


def _testnet_rows(n, seed=0):
    mf = getModelFunction("TestNet", featurize=True)
    shape = tuple(mf.input_signature["image"][0])
    rows = np.random.default_rng(seed).integers(
        0, 255, size=(n,) + shape, dtype=np.uint8)
    return mf, rows


def _frame(rows, partitions):
    import pyarrow as pa
    batches = []
    for part in np.array_split(rows, partitions):
        empty = pa.RecordBatch.from_arrays(
            [pa.array(np.arange(len(part)))], names=["i"])
        batches.append(append_tensor_column(empty, "image", part))
    return DataFrame.from_batches(batches)


class TestParents:
    def test_ids_nest_on_one_thread(self):
        t = Tracer(capacity=16)
        t.arm()
        with t.span("outer"):
            with t.span("middle"):
                with t.span("inner"):
                    pass
            with t.span("sibling"):
                pass
        s = _by_name(t.spans())
        assert s["outer"].parent_id == 0
        assert s["middle"].parent_id == s["outer"].span_id
        assert s["inner"].parent_id == s["middle"].span_id
        assert s["sibling"].parent_id == s["outer"].span_id
        ids = [r.span_id for r in t.spans()]
        assert len(set(ids)) == 4 and all(ids)

    def test_parents_are_thread_local(self):
        """Two threads with interleaved spans: each span's parent is
        the one open on ITS thread, never the other thread's."""
        t = Tracer(capacity=16)
        t.arm()
        a_open, b_open = threading.Event(), threading.Event()

        def worker(name, mine, theirs):
            with t.span(f"{name}.outer"):
                mine.set()
                assert theirs.wait(5)
                with t.span(f"{name}.inner"):
                    pass

        threads = [threading.Thread(target=worker, args=("a", a_open, b_open)),
                   threading.Thread(target=worker, args=("b", b_open, a_open))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
            assert not th.is_alive()
        s = _by_name(t.spans())
        assert len(s) == 4
        for name in "ab":
            assert s[f"{name}.outer"].parent_id == 0
            assert s[f"{name}.inner"].parent_id == s[f"{name}.outer"].span_id
            assert s[f"{name}.inner"].thread_id == s[f"{name}.outer"].thread_id

    def test_out_of_order_close_keeps_the_others(self):
        t = Tracer(capacity=16)
        t.arm()
        first, second = t.span("first"), t.span("second")
        first.__enter__()
        second.__enter__()
        first.__exit__(None, None, None)
        with t.span("child"):
            pass
        second.__exit__(None, None, None)
        s = _by_name(t.spans())
        assert s["child"].parent_id == s["second"].span_id
        with t.span("after"):
            pass
        assert _by_name(t.spans())["after"].parent_id == 0

    def test_export_carries_ids(self, tmp_path):
        t = Tracer(capacity=16)
        t.arm()
        with t.span("outer"):
            with t.span("inner"):
                pass
        path = tmp_path / "spans.json"
        t.export(str(path))
        events = {e["name"]: e for e in json.loads(path.read_text())
                  if e.get("ph") == "X"}
        assert (events["inner"]["args"]["parent_id"]
                == events["outer"]["args"]["span_id"])

    def test_device_get_takes_the_enclosing_span(self, armed):
        with armed.span("drain"):
            timed_device_get(jax.numpy.ones((2,)))
        s = _by_name(armed.spans())
        assert s["device_get"].parent_id == s["drain"].span_id
        timed_device_get(jax.numpy.ones((2,)))
        assert armed.spans()[-1].parent_id == 0

    def test_compile_span_takes_the_enclosing_span(self, armed):
        mf = ModelFunction.fromSingle(lambda x: x * 3.0, None,
                                      input_shape=(5,), name="tripler")
        with armed.span("warm"):
            mf.jitted()(None, {"input": np.ones((2, 5), np.float32)})
        s = _by_name(armed.spans())
        assert s["compile"].attrs["fn"] == "tripler.jitted"
        assert s["compile"].parent_id == s["warm"].span_id


class TestRunnerSpans:
    @pytest.mark.parametrize("sharded", [False, True],
                             ids=["single_device", "four_devices"])
    def test_span_times_what_the_counter_times(self, armed, sharded):
        mf, rows = _testnet_rows(24)
        if sharded:
            runner = ShardedBatchRunner(
                mf, make_mesh(devices=jax.devices()[:4]), batch_size=2)
            name = "runner.run_sharded"
        else:
            runner = BatchRunner(mf, batch_size=8)
            name = "runner.run"
        runner.run({"image": rows})  # compile outside the compared run
        armed.clear()
        before = runner.metrics.seconds
        runner.run({"image": rows})
        added = runner.metrics.seconds - before
        spans = armed.spans()
        (run,) = [r for r in spans if r.name == name]
        assert abs((run.end - run.start) - added) < 1e-3
        # everything the run did on this thread descends from it
        children = [r for r in spans if r.parent_id == run.span_id]
        assert {"dispatch", "device_get"} <= {r.name for r in children}
        assert all(run.start <= r.start and r.end <= run.end
                   for r in children)

    def test_transform_splits_the_handoff(self, armed):
        mf, rows = _testnet_rows(12)
        t = TensorTransformer(modelFunction=mf,
                              inputMapping={"image": "image"},
                              outputMapping={"features": "features"},
                              batchSize=4)
        out = t.transform(_frame(rows, 3)).collect()
        assert out.num_rows == 12
        spans = armed.spans()
        names = [r.name for r in spans]
        assert names.count("transform.plan") == 1
        assert names.count("runner.run") == 3
        by_id = {r.span_id: r for r in spans}
        for r in spans:
            if r.name in ("transform.to_tensors", "transform.append_columns",
                          "runner.run"):
                assert by_id[r.parent_id].name.startswith("stage:apply(")
        # of one partition, in order: to tensors, the run, the append
        stage = next(r for r in spans if r.name.startswith("stage:"))
        mine = sorted((r for r in spans if r.parent_id == stage.span_id),
                      key=lambda r: r.start)
        assert [r.name for r in mine] == [
            "transform.to_tensors", "runner.run", "transform.append_columns"]

    def test_disarmed_transform_records_nothing(self, monkeypatch):
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        monkeypatch.delenv("SPARKDL_TPU_COMPILE_LOG", raising=False)
        t, log = tracer(), compile_log()
        t.arm_from_env()
        log.arm_from_env()
        t.clear()
        log.clear()
        mf, rows = _testnet_rows(8, seed=1)
        tt = TensorTransformer(modelFunction=mf,
                               inputMapping={"image": "image"},
                               outputMapping={"features": "features"},
                               batchSize=4)
        assert tt.transform(_frame(rows, 2)).collect().num_rows == 8
        assert t.spans() == []
        assert log.events() == []
        assert t._open_spans() == []


class TestProgramName:
    @pytest.mark.parametrize("variant", ["jitted", "sharded"])
    def test_module_is_named_after_the_model(self, variant):
        def some_apply(params, inputs):
            return {"output": inputs["input"] + 1.0}

        def wrapped_again(params, inputs):
            return some_apply(params, inputs)

        x = {"input": np.zeros((4, 3), np.float32)}
        names = []
        for apply_fn in (some_apply, wrapped_again):
            mf = ModelFunction(apply_fn, None, {"input": ((3,), np.float32)},
                               ["output"], name="Net-v2:featurize")
            if variant == "sharded":
                fn = mf.sharded_jitted(make_mesh(devices=jax.devices()[:4]))
            else:
                fn = mf.jitted()
            text = fn.lower(None, x).as_text()
            names.append(text.split("module @", 1)[1].split()[0])
        assert names == ["jit_Net_v2_featurize"] * 2


    def test_one_label_over_one_apply_fn_is_one_compile(self, armed):
        """A fleet's replicas: ModelFunctions over one ``apply_fn``
        under one program label share jax's compile of it, as they
        did when ``apply_fn`` itself was jitted."""
        def apply(params, inputs):
            return {"output": inputs["input"] @ params["w"]}

        params = {"w": np.eye(4, dtype=np.float32)}
        x = {"input": np.ones((8, 4), np.float32)}
        replicas = []
        for i in range(2):
            mf = ModelFunction(apply, params, {"input": ((4,), np.float32)},
                               ["output"], name=f"shared@r{i}")
            mf._program_name = "shared"
            mf.jitted()(mf.device_params(), x)
            replicas.append(mf)
        assert compile_log().compiles_of("shared@r0.jitted") == 1
        assert compile_log().compiles_of("shared@r1.jitted") == 0
        text = replicas[1].jitted().lower(params, x).as_text()
        assert "module @jit_shared " in text


class TestScopeMap:
    def test_two_blocks_are_both_in_the_map(self, armed):
        mf, rows = _testnet_rows(4)
        mf.jitted()(mf.device_params(), {"image": rows})
        (event,) = compile_log().events_for("TestNet:featurize.jitted")
        assert event.module == "jit_TestNet_featurize"
        paths = set(event.scopes.values())
        assert any(p.startswith("TestNet/ConvBN_0") for p in paths)
        assert any(p.startswith("TestNet/ConvBN_1") for p in paths)
        assert not any("jit(" in p for p in paths)
        # the placement of the weights is an event too, with no program
        (put,) = compile_log().events_for("TestNet:featurize.device_params")
        assert put.scopes is None and put.module is None

    def test_nothing_is_kept_disarmed(self):
        log = CompileLog(capacity=8)
        mf = ModelFunction.fromSingle(lambda x: x + 1.0, None,
                                      input_shape=(3,), name="plain")
        fn = log.instrument(jax.jit(mf.apply_fn), name="plain.jitted")
        fn(None, {"input": np.ones((2, 3), np.float32)})
        assert log.events() == []

    def test_parse(self):
        text = "\n".join([
            "HloModule jit_Net_featurize, is_scheduled=true",
            "%fused_computation.2 (p: f32[4]) -> f32[4] {",
            '  %p = f32[4]{0} parameter(0), metadata={op_name="params[\'w\']"}',
            '  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata='
            '{op_name="jit(Net_featurize)/Net/Block_0/Dense_0/add"}',
            "}",
            "ENTRY %main {",
            '  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, calls='
            '%fused_computation.2, metadata={op_name="jit(Net_featurize)/'
            'Net/Block_0/jit(relu)/max" source_file="x.py"}',
            '  %convert.7 = f32[4]{0} convert(%b), metadata='
            '{op_name="jit(Net_featurize)/convert_element_type"}',
            "  %copy.3 = f32[4]{0} copy(%c)",
            "}"])
        module, scopes = instruction_scopes(text)
        assert module == "jit_Net_featurize"
        assert scopes == {"add.1": "Net/Block_0/Dense_0",
                          "fusion.2": "Net/Block_0"}


class TestOperatorsTrace:
    def test_host_tracer_off_and_state_restored(self, tmp_path, monkeypatch):
        calls = {}

        def start_trace(log_dir, **kwargs):
            calls["start"] = (log_dir, kwargs)

        def stop_trace():
            calls["stop"] = True

        monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
        monkeypatch.delenv("SPARKDL_TPU_TRACE", raising=False)
        monkeypatch.delenv("SPARKDL_TPU_COMPILE_LOG", raising=False)
        t, log = tracer(), compile_log()
        t.arm_from_env()
        log.disarm()  # an override that must come back as it was
        t.clear()
        try:
            with profiling.trace(str(tmp_path)):
                assert t.armed and log.armed
                with t.span("work", lane="engine"):
                    pass
            assert not t.armed and t._override is None
            assert not log.armed and log._override is False
        finally:
            log.arm_from_env()
            t.clear()
        options = calls["start"][1]["profiler_options"]
        assert options.host_tracer_level == 0
        assert options.python_tracer_level == 0
        assert "create_perfetto_link" not in calls["start"][1]
        assert calls["stop"]
        events = {e["name"]: e for e in json.loads(
            (tmp_path / "program_spans.json").read_text())
            if e.get("ph") == "X"}
        # the zero of the device trace's clock, on the spans' clock
        start = events["profiler.start_trace"]
        assert start["args"]["perf_counter"] > 0
        assert start["ts"] <= events["work"]["ts"]
        assert events["work"]["ts"] <= events["profiler.stop_trace"]["ts"]
