"""The cell ``ouro_score_stream`` on the CPU at its traffic file's rehearsal widths: a run of
the cell end to end under the driver ``token_stream``, ``correct`` false for the int8
control, for a loop one pass short and for one without the final norm in it and true for a
sound run, the configuration's file against the catalog's numbers and ISSUE 34's counts,
``kernel_work.attention`` over the loop's passes against a hand count, and the new metric
files."""

import json
import os

import numpy as np
import pytest

from benchmarks import kernel_work, lm_weights, model, program_lm, run as bench_run
from benchmarks.comparers import logprob_rows
from benchmarks.drivers import token_stream

CELL = "ouro_score_stream"
SUFFIXED = ("engine.outside_runner_share", "runner.transfer_wait_share", "model.step_ms",
            "model.step_mfu", "device.idle_share", "device.peak_hbm_gb", "model.attention_share",
            "model.mlp_share", "model.attn_proj_share", "kernel.looped_attention_roofline")
NEW_FILES = ("model.mlp_share", "model.attn_proj_share", "kernel.looped_attention_roofline")


def compare_rows(answers, pdf, reference, reference_pdf, spec):
    return logprob_rows.compare_outputs({"logprobs": answers, "exit_pdf": pdf},
                                        {"logprobs": reference, "exit_pdf": reference_pdf},
                                        "logprobs", spec)


def rehearsal_config():
    config = model.load_config("benchmarks/configs/ouro_2p6b.json")
    traffic = model.load_config("benchmarks/traffic/tokens_stream_4k.json")
    config.update(traffic["rehearsal"]["config"])
    return config, traffic["rehearsal"]


@pytest.fixture(scope="module")
def readings():
    """The reference over six rows of the rehearsal's traffic, and each stand-in over them."""
    config, traffic = rehearsal_config()
    seed = 2**31 + 5
    weights = lm_weights.make_weights(config, seed)
    tokens = lm_weights.token_rows(seed, 6, traffic["row_tokens"], config["vocab_size"], 1.0)
    sound = lm_weights.reference_outputs(config, weights, tokens, outputs=["exit_pdf"])
    return config, traffic, weights, tokens, sound


def test_a_sound_program_is_correct(readings):
    config, traffic, weights, tokens, (answers, pdf) = readings
    program = program_lm.model_function(config, weights, traffic["row_tokens"])
    out = program({"tokens": tokens})
    ok, compared = compare_rows(
        np.asarray(out["logprobs"]), np.asarray(out["exit_pdf"]), answers, pdf, config["correct"])
    assert ok, compared
    assert set(compared) == {"centred_err_max", "centred_err_p50", "flatness_max",
                             "rows_compared", "exit_pdf_err_max"}
    # a sound answer whose exit distribution is another row's is not correct, by that limit alone
    ok, compared = compare_rows(
        np.asarray(out["logprobs"]), np.asarray(out["exit_pdf"])[::-1], answers, pdf, config["correct"])
    assert not ok and compared["centred_err_max"]["value"] < compared["centred_err_max"]["limit"]
    assert compared["exit_pdf_err_max"]["value"] > compared["exit_pdf_err_max"]["limit"]


@pytest.mark.parametrize("stand_in", [{"quant": "int8"}, {"passes": 3}, {"norm_in_loop": False}],
                         ids=["int8_control", "three_passes", "norm_outside_loop"])
def test_a_stand_in_is_not_correct(readings, stand_in):
    config, _, weights, tokens, (answers, pdf) = readings
    wrong, wrong_pdf = lm_weights.reference_outputs(config, weights, tokens, outputs=["exit_pdf"],
                                                    **stand_in)
    if wrong_pdf.shape != pdf.shape:  # a pass short
        wrong_pdf = np.concatenate([wrong_pdf, np.zeros((len(pdf), 1))], axis=1)
    ok, compared = compare_rows(wrong, wrong_pdf, answers, pdf, config["correct"])
    assert not ok, compared
    assert compared["centred_err_max"]["value"] > compared["centred_err_max"]["limit"]


def test_the_traffic_is_cell_4s_law_over_the_whole_vocabulary_in_rows_of_4096():
    mine = model.load_config("benchmarks/traffic/tokens_stream_4k.json")
    cell5 = model.load_config("benchmarks/traffic/tokens_stream_p16.json")
    assert (mine["row_tokens"], mine["device_batch"], mine["zipf_exponent"]) == (4096, 2, 1.0)
    assert mine["partition_rows"] * mine["partitions_per_pass"] == 8
    assert {k: mine[k] for k in ("rate_metric", "use_mesh", "trace_seconds", "zipf_exponent")} == \
        {k: cell5[k] for k in ("rate_metric", "use_mesh", "trace_seconds", "zipf_exponent")}
    rows = lm_weights.token_rows(2**31 + 21, 8, mine["row_tokens"], 49152, mine["zipf_exponent"])
    assert rows.dtype == np.int32 and rows.min() >= 0 and rows.max() < 49152
    assert rows.max() > 40000  # over all 49,152 ids, not a slice
    assert len({row.tobytes() for row in rows}) == 8  # every row of a pass distinct
    share = np.mean(rows == 0)  # 1 / H(49,152) = 8.8% of all tokens
    assert 0.08 < share < 0.095


def test_a_rehearsal_run_of_the_cell(capsys):
    assert bench_run.main(["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
                           "--trace", "1", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] is True
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])
    for name in ("engine.outside_runner_share.loop", "runner.transfer_wait_share.loop"):
        assert "cpu_rehearsal." + name in result["metrics"], sorted(result["metrics"])
    # each partition's first and last row, of the first timed pass and of the last
    assert result["compared"]["rows_compared"]["value"] in (4, 8)
    assert result["compared"]["exit_pdf_err_max"]["limit"] == 0.0023
    passes = [line for line in out if line.startswith("pass ")]
    assert any("(traced)" in line for line in passes) and "(traced)" not in passes[-1]
    # the loop's counters, as the configuration's recorder of exit_pdf moved them
    window = next(line for line in out if line.startswith("window: "))
    counters = dict(part.split(" ") for part in window.split("; ")[1:])
    assert float(counters["loop.rows"]) == result["attempted"]
    assert 1.0 < float(counters["loop.exit_step_mean"]) < 4.0


def test_the_driver_counts_the_windows_rows_into_the_loops_counters(monkeypatch):
    from benchmarks import harness
    from sparkdl_tpu.obs.registry import default_registry
    bench = model.load_config("BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    import time
    import jax
    before = default_registry().snapshot()
    run = harness.Run(cell=cell, config=model.load_config("benchmarks/configs/ouro_2p6b.json"),
                      traffic=model.load_config("benchmarks/traffic/tokens_stream_4k.json"),
                      seed=2**31 + 12, seconds=0.2, trace=False, rehearsal=True,
                      started=time.perf_counter(), devices=jax.devices()[:1])
    outcome = token_stream.run(run)
    after = default_registry().snapshot()
    assert outcome.failed == 0 and outcome.attempted % 10 == 0
    assert outcome.observed["loop.rows"] == outcome.attempted
    assert after["loop.rows"] - before.get("loop.rows", 0) == outcome.attempted
    assert 1.0 < outcome.observed["loop.exit_step_mean"] < 4.0
    # every kept row's exit distribution has total_ut_steps entries that sum to 1
    assert outcome.evidence["exit_pdf"].shape == (len(outcome.evidence["inputs"]), 4)
    np.testing.assert_allclose(outcome.evidence["exit_pdf"].sum(axis=1), 1.0, atol=1e-3)
    assert outcome.evidence["logprobs"].shape == (len(outcome.evidence["inputs"]), 47)
    outcome.release()


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    bench = model.load_config("BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro_2p6b", "tokens_stream_4k", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2p6b")
    assert entry["reduced"] == [] and entry["file"] == "benchmarks/configs/ouro_2p6b.json"
    assert entry["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    rate = next(m for m in bench["end_to_end"] if m["name"] == "rows_per_s")
    assert rate["workloads"][-1] == CELL
    mine = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {name + ".loop" for name in SUFFIXED}
    for name, metric in mine.items():
        assert metric["moves"] == "rows_per_s"
        spec = bench_run._metric_spec(name)  # found by the longest dotted prefix
        assert spec["name"] == name[:-len(".loop")], (name, spec["name"])
        assert os.path.exists(os.path.join(os.path.dirname(__file__), "..", "readers",
                                           spec["reader"] + ".py")), spec["reader"]
    # the share of the whole step's peak and every `_roofline` name read between 0 and 100
    assert {m["unit"] for n, m in mine.items() if "roofline" in n or "mfu" in n} == {"%"}


@pytest.mark.parametrize("name", NEW_FILES)
def test_a_new_metric_file_loads_and_shadows_no_older_one(name):
    spec = model.load_config(f"benchmarks/metrics/{name}.json")
    assert spec["name"] == name
    assert spec["reader"] in ("trace_kernel_share", "trace_kernel_roofline")
    files = {f[:-len(".json")] for f in os.listdir(os.path.join(os.path.dirname(__file__), "..", "metrics"))}
    assert not any(name.startswith(other + ".") for other in files - {name})
    if "work" in spec["params"]:
        module, function = spec["params"]["work"].rsplit(".", 1)
        assert (module, getattr(kernel_work, function)) == ("kernel_work", kernel_work.attention)
    # no scope map, or a program from before the scope existed (the parent): nothing, or 0
    from benchmarks.readers import trace_kernel_roofline, trace_kernel_share
    reader = {"trace_kernel_share": trace_kernel_share,
              "trace_kernel_roofline": trace_kernel_roofline}[spec["reader"]]
    view = {"observed": {"program.scopes": None}, "trace": None, "config": {}, "peaks": None}
    assert reader.read(view, spec["params"]) is None


def test_the_file_holds_the_catalogs_numbers_and_gives_the_issues_counts():
    config = model.load_config("benchmarks/configs/ouro_2p6b.json")
    catalog = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
               "max_position_embeddings": 65536, "max_window_layers": 48, "model_type": "ouro",
               "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
               "sliding_window": None, "tie_word_embeddings": False, "total_ut_steps": 4,
               "early_exit_threshold": 1, "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in catalog} == catalog
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == [] and config["input_shape"] == [4096]
    assert set(config["assumed"]) >= {"four_norm_layer", "final_norm_in_loop", "exit_gate",
                                      "weights", "traffic", "head_gain", "exit_gate_gain"}
    specs = model._survey(config).specs  # shapes alone: nothing is allocated
    assert sum(int(np.prod(s[0])) for s in specs.values()) == 2_667_974_657  # 5.34 GB in bfloat16
    assert specs["layers/q_proj"][0] == (48, 2048, 2048) and specs["layers/down"][0] == (48, 5632, 2048)
    # ISSUE 34: 19.73 + 0.20 + 3.22 = 23.15 GFLOP a token, 94.8 TFLOP a row, four passes counted
    flops = model.flops_per_row(config)
    assert round(flops / 1e12, 1) == 94.8
    one_pass = model.flops_per_row(dict(config, total_ut_steps=1))
    head = 2 * 4095 * 2048 * 49152
    assert flops - head == 4 * (one_pass - head)
    layer = 2 * 4096 * 51_380_224 + 4 * (4096 * 4096 // 2) * 128 * 16
    assert one_pass - head == 48 * layer + 2 * 4096 * 2048


def test_kernel_work_against_a_hand_count():
    config = {"num_hidden_layers": 3, "total_ut_steps": 4, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16}
    rows, tokens = 2, 10
    # 3 layers x 4 passes; half of 10 x 10 scores, 2 rows, 4 heads, two products 16 wide
    work = kernel_work.attention(config, rows, tokens)
    assert work == {"calls": 12, "flops": 12 * 2 * 2 * 2 * 50 * 4 * 16,
                    # q (4 heads), k and v (2 each) in and o (4 heads) out at 2 bytes, 16 wide
                    "bytes": 12 * 20 * 16 * (2 * (4 + 2 + 2) + 2 * 4)}
    # one pass: a model in which every layer is full attention, each counted once
    one_pass = kernel_work.attention(dict(config, total_ut_steps=1), rows, tokens)
    assert work == {k: 4 * v for k, v in one_pass.items()}
    assert one_pass == kernel_work.attention(
        {k: v for k, v in config.items() if k != "total_ut_steps"}, rows, tokens)
    # the published shapes: 192 calls, compute-bound
    published = model.load_config("benchmarks/configs/ouro_2p6b.json")
    step = kernel_work.attention(published, 2, 4096)
    assert step["calls"] == 192 and step["flops"] / 197e12 > step["bytes"] / 819e9
    assert round(step["flops"] / 1e12, 2) == 26.39  # 3.22 GFLOP a token x 8,192
