"""``correct`` has to come out false for the control and for a timed path that is
broken underneath, and true for a sound run. These drive the real configurations at
the traffic files' rehearsal sizes on the CPU: a minute or so each."""

import json

import numpy as np
import pytest

from benchmarks import harness, model, program, run as bench_run
from benchmarks.comparers import rows_rel_err

CONFIGS = ["inceptionv3_featurize"]
CELLS = ["inceptionv3_featurize_stream", "inceptionv3_featurize_stream_x4"]


def test_row_gaps_measure_each_row_against_its_reference():
    ref = np.array([[3.0, 4.0], [1.0, 0.0]])
    gaps = rows_rel_err.row_gaps(np.array([[3.0, 4.5], [1.0, 0.0]]), ref)
    assert gaps == pytest.approx([0.1, 0.0])
    assert rows_rel_err.row_gaps(np.array([[np.nan, 4.0], [1.0, 0.0]]), ref)[0] == np.inf
    with pytest.raises(ValueError):
        rows_rel_err.row_gaps(ref[:1], ref)


def test_compare_holds_each_number_to_its_own_limit():
    ref = np.ones((4, 8))
    ok, compared = rows_rel_err.compare_rows(ref * 1.01, ref, {"limits": {"rel_err_max": {"limit": 0.02}}})
    assert ok and compared["rel_err_max"]["value"] == pytest.approx(0.01)
    assert compared["rows_compared"]["value"] == 4
    ok, _ = rows_rel_err.compare_rows(ref * 1.03, ref, {"limits": {"rel_err_max": {"limit": 0.02}}})
    assert not ok
    ok, _ = rows_rel_err.compare_rows(ref, ref, {"limits": {"rel_err_max": {"limit": None}}})
    assert not ok  # a number without a limit proves nothing
    with pytest.raises(KeyError):
        rows_rel_err.compare_rows(ref, ref, {"limits": {"no_such_number": {"limit": 1.0}}})


@pytest.mark.parametrize("name", CONFIGS)
def test_the_int8_control_is_not_correct(name):
    config = model.load_config(f"benchmarks/configs/{name}.json")
    weights = model.make_weights(config, 2**31 + 5)
    images = harness.image_rows(2**31 + 5, 4, config["input_shape"])
    reference = model.reference_outputs(config, weights, images, block=4)
    control = model.reference_outputs(config, weights, images, quant="int8", block=4)
    ok, compared = rows_rel_err.compare_rows(control, reference, config["correct"])
    assert not ok, compared
    ok, compared = rows_rel_err.compare_rows(reference, reference, config["correct"])
    assert ok, compared


def _result(capsys, cell, seed):
    assert bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                           "--trace", "0", "--rehearsal", "1"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_prints_no_device_metric(capsys, cell):
    result = _result(capsys, cell, 2**31 + 11)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] is True
    assert result["metrics"] and all(k.startswith("cpu_rehearsal.") for k in result["metrics"])
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch, cell):
    sound = program.model_function

    def altered(config, weights):
        mf = sound(config, weights)
        inner = mf.apply_fn

        def apply_fn(params, inputs):
            out = inner(params, inputs)
            # the first row of every device batch answers with its neighbour's values
            return {k: v.at[0].set(v[1]) for k, v in out.items()}

        mf.apply_fn = apply_fn
        return mf

    monkeypatch.setattr(program, "model_function", altered)
    result = _result(capsys, cell, 2**31 + 12)
    assert result["correct"] is False
    assert result["compared"]["rel_err_max"]["value"] > result["compared"]["rel_err_max"]["limit"]


def test_a_traced_run_reads_the_counters_over_its_untraced_passes(capsys):
    assert bench_run.main(["--workload", CELLS[0], "--seed", str(2**31 + 13), "--seconds", "1",
                           "--trace", "1", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    passes = [line for line in out if line.startswith("pass ")]
    assert any("(traced)" in line for line in passes) and "(traced)" not in passes[-1]
    share = result["metrics"]["cpu_rehearsal.engine.outside_runner_share.batch"]["value"]
    assert 0.0 < share < 100.0
    assert result["correct"] is True, result["compared"]


def test_a_metric_file_serves_every_suffix_of_its_name():
    assert bench_run._metric_spec("model.step_ms.batch") == bench_run._metric_spec("model.step_ms.mesh")
    assert bench_run._metric_spec("mesh.idle_share_max.mesh")["params"] == {"which": "max"}
    with pytest.raises(FileNotFoundError):
        bench_run._metric_spec("no.such.metric")


def test_a_metric_without_workloads_is_read_where_its_end_to_end_metric_is():
    cell = {"name": "a"}
    assert bench_run._applies({"moves": "rows_per_s", "workloads": ["a"]}, cell, set())
    assert not bench_run._applies({"moves": "rows_per_s", "workloads": ["b"]}, cell, {"rows_per_s"})
    assert bench_run._applies({"moves": "rows_per_s"}, cell, {"rows_per_s", "setup_s"})
    assert not bench_run._applies({"moves": "mesh_rows_per_s"}, cell, {"rows_per_s", "setup_s"})
