"""The five ``setup.*`` metrics (ISSUE 36): the reader ``registry_sum`` over the
program's own registry, and a traced rehearsal of cell 1 that prints all five."""

import json

import pytest

from benchmarks import harness, run as bench_run
from benchmarks.readers import registry_sum

SETUP_METRICS = ["setup.trace_lower_s", "setup.backend_compile_s", "setup.cache_miss_share",
                 "setup.params_place_s", "setup.program_compiles"]
CELLS = ["inceptionv3_featurize_stream", "inceptionv3_featurize_stream_x4",
         "qwen3next_score_stream", "axk1_score_stream", "ouro_score_stream"]


@pytest.fixture()
def counters():
    """Three counters of this test's own in the program's registry."""
    from sparkdl_tpu.obs import default_registry
    registry = default_registry()
    made = {name: registry.counter(f"test_setup_metrics.{name}") for name in ("a", "b", "none")}
    made["a"].add(1.5)
    made["b"].add(2.5)
    yield {name: (c.name, c.value) for name, c in made.items()}


def test_a_counter_never_made_reads_nothing(counters):
    a, _ = counters["a"]
    assert registry_sum.read({}, {"counters": ["test_setup_metrics.never_made"]}) is None
    assert registry_sum.read({}, {"counters": [a, "test_setup_metrics.never_made"]}) is None
    assert registry_sum.read({}, {"counters": [a], "per": ["test_setup_metrics.never_made"]}) is None


def test_the_sum_is_scaled(counters):
    (a, va), (b, vb) = counters["a"], counters["b"]
    assert registry_sum.read({}, {"counters": [a, b]}) == pytest.approx(va + vb)
    assert registry_sum.read({}, {"counters": [a], "scale": 1e3}) == pytest.approx(1e3 * va)
    # a counter that was made and never moved is a reading, 0, and not nothing
    assert registry_sum.read({}, {"counters": [counters["none"][0]]}) == 0.0


def test_the_ratio_form_and_its_complement(counters):
    (a, va), (b, vb) = counters["a"], counters["b"]
    assert registry_sum.read({}, {"counters": [a], "per": [a, b], "scale": 100.0}) \
        == pytest.approx(100.0 * va / (va + vb))
    assert registry_sum.read({}, {"counters": [a], "per": [a, b], "complement": True,
                                  "scale": 100.0}) == pytest.approx(100.0 * vb / (va + vb))
    # nothing was asked: no share of it
    assert registry_sum.read({}, {"counters": [a], "per": [counters["none"][0]]}) is None


def test_every_cell_lists_the_five_and_each_has_its_file():
    bench = bench_run._load("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SETUP_METRICS:
        entry = by_name[name]
        assert entry["moves"] == "setup_s" and entry["layer"] == "set-up"
        assert entry["source"] == "program_counter" and entry["better"] == "lower"
        assert entry["workloads"] == CELLS
        spec = bench_run._metric_spec(name)
        assert spec["name"] == name and spec["reader"] == "registry_sum"
    assert [m["name"] for m in bench["per_layer"][-5:]] == SETUP_METRICS


def test_a_traced_rehearsal_of_cell_1_prints_all_five(capsys, monkeypatch, tmp_path):
    # a trace directory of this test's own: harness.Tracer's is the checkout's, which
    # every traced rehearsal of every worker shares and removes at its start
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert bench_run.main(["--workload", CELLS[0], "--seed", str(2**31 + 36), "--seconds", "1",
                           "--trace", "1", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    assert all(k.startswith("cpu_rehearsal.") for k in metrics)
    read = {name: metrics[f"cpu_rehearsal.{name}"] for name in SETUP_METRICS}
    assert [read[name]["unit"] for name in SETUP_METRICS] == ["s", "s", "%", "s", "count"]
    assert read["setup.trace_lower_s"]["value"] > 0.0
    assert read["setup.backend_compile_s"]["value"] > 0.0
    assert read["setup.params_place_s"]["value"] > 0.0
    assert 0.0 <= read["setup.cache_miss_share"]["value"] <= 100.0
    # the registry is the process's, and a worker may have compiled other programs
    # before this run: at least this one
    assert read["setup.program_compiles"]["value"] >= 1.0
