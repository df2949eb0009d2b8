"""The cell ``axk1_score_stream`` on the CPU at its traffic file's rehearsal widths: a run
of the cell end to end under the driver ``token_stream``, ``correct`` false for the int8
control and for a reference with the shared rotary key left out and true for a sound run,
the configuration's file against the catalog's widths and the reckoned counts,
``kernel_work.latent_attention`` and ``.moe_experts`` against hand counts, and the reader
``trace_kernel_roofline`` told that work over the recorded v5e trace."""

import json
import os

import jax
import numpy as np
import pytest

from benchmarks import kernel_work, lm_weights, model, program_lm, run as bench_run, tracing
from benchmarks.comparers import logprob_rows
from benchmarks.readers import trace_kernel_roofline, trace_kernel_share
from benchmarks.reference import axk1 as reference
from benchmarks.reference.nn import Net

CELL = "axk1_score_stream"
DATA = os.path.join(os.path.dirname(__file__), "data")
SUFFIXED = ("engine.outside_runner_share", "runner.transfer_wait_share", "model.step_ms",
            "model.step_mfu", "device.idle_share", "device.peak_hbm_gb", "model.attention_share",
            "model.moe_share", "moe.held_assignment_share", "moe.expert_load_max_over_mean",
            "model.latent_proj_share", "kernel.latent_attention_roofline",
            "kernel.routed_experts_roofline")


def rehearsal_config():
    config = model.load_config("benchmarks/configs/axk1_ep16.json")
    traffic = model.load_config("benchmarks/traffic/tokens_stream_p16.json")
    config.update(traffic["rehearsal"]["config"])
    return config, traffic["rehearsal"]


def test_the_int8_control_and_a_missing_rotary_key_are_not_correct_and_a_sound_program_is():
    config, traffic = rehearsal_config()
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 3
    seed = 2**31 + 5
    weights = lm_weights.make_weights(config, seed)
    tokens = lm_weights.token_rows(seed, 6, traffic["row_tokens"], config["vocab_size"], 1.0)
    answers = lm_weights.reference_outputs(config, weights, tokens)
    control = lm_weights.reference_outputs(config, weights, tokens, quant="int8")
    ok, compared = logprob_rows.compare_rows(control, answers, config["correct"])
    assert not ok, compared
    no_rope_key = jax.jit(lambda w, t: reference.forward(
        Net(params=w), t, config, use_rope_key=False)["logprobs"])(weights, tokens)
    ok, compared = logprob_rows.compare_rows(np.asarray(no_rope_key), answers, config["correct"])
    assert not ok, compared
    program = program_lm.model_function(config, weights, traffic["row_tokens"])
    ok, compared = logprob_rows.compare_rows(np.asarray(program(tokens)), answers,
                                             config["correct"])
    assert ok, compared


def test_the_traffic_is_cell_4s_law_over_this_vocabulary_in_passes_of_16():
    """ISSUE 32's traffic: ids by one Zipf law of exponent 1 over the 20,480 ids, rank = id
    + 1 in every row as in ``tokens_stream``, nothing but the pass's size changed."""
    mine = model.load_config("benchmarks/traffic/tokens_stream_p16.json")
    cell4 = model.load_config("benchmarks/traffic/tokens_stream.json")
    differs = {k for k in set(mine) | set(cell4) if mine.get(k) != cell4.get(k)}
    assert differs == {"what", "partitions_per_pass", "rehearsal"}
    assert mine["partition_rows"] * mine["partitions_per_pass"] == 16
    rows = lm_weights.token_rows(2**31 + 21, 16, mine["row_tokens"], 20480, mine["zipf_exponent"])
    assert rows.dtype == np.int32 and rows.min() >= 0 and rows.max() < 20480
    assert len({row.tobytes() for row in rows}) == 16  # every row of a pass distinct
    assert {int(np.bincount(row).argmax()) for row in rows} == {0}  # one order of the ids for all
    share = np.mean(rows == 0)  # 1 / H(20,480) = 9.5% of all tokens
    assert 0.09 < share < 0.10


def test_a_rehearsal_run_of_the_cell(capsys):
    assert bench_run.main(["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
                           "--trace", "1", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] is True
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])
    for name in ("engine.outside_runner_share.mla", "runner.transfer_wait_share.mla",
                 "moe.held_assignment_share.mla", "moe.expert_load_max_over_mean.mla"):
        assert "cpu_rehearsal." + name in result["metrics"], sorted(result["metrics"])
    # 3 of the router's 8 held, two choices a token, over the three layers that route: a
    # count over all four layers would read three quarters of this
    share = result["metrics"]["cpu_rehearsal.moe.held_assignment_share.mla"]["value"]
    assert 25.0 < share < 60.0
    assert result["metrics"]["cpu_rehearsal.moe.expert_load_max_over_mean.mla"]["value"] >= 1.0
    assert result["compared"]["rows_compared"]["value"] in (8, 16)
    passes = [line for line in out if line.startswith("pass ")]
    assert any("(traced)" in line for line in passes) and "(traced)" not in passes[-1]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    bench = model.load_config("BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("axk1_ep16", "tokens_stream_p16", 1)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "rows_per_s")
    assert CELL in rate["workloads"]
    mine = {m["name"]: m for m in bench["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {name + ".mla" for name in SUFFIXED}
    for name, metric in mine.items():
        assert metric["moves"] == "rows_per_s"
        spec = bench_run._metric_spec(name)  # found by the longest dotted prefix
        assert spec["name"] == name[:-len(".mla")], (name, spec["name"])
    # a new metric's name has no older metric file's name as a dotted prefix
    files = {f[:-len(".json")] for f in os.listdir(os.path.join(os.path.dirname(DATA), "..", "metrics"))}
    for new in ("model.latent_proj_share", "kernel.latent_attention_roofline",
                "kernel.routed_experts_roofline"):
        assert not any(new.startswith(other + ".") for other in files - {new})


def test_the_file_holds_the_catalogs_widths_and_gives_the_issues_counts():
    config = model.load_config("benchmarks/configs/axk1_ep16.json")
    widths = {"hidden_size": 7168, "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 18432,
              "moe_intermediate_size": 2048, "num_attention_heads": 64, "num_experts_per_tok": 8,
              "router_width": 192, "first_k_dense_replace": 1, "routed_scaling_factor": 2.5}
    assert {k: config[k] for k in widths} == widths
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 61, "n_routed_experts": 192,
                                   "vocab_size": 163840}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        6, 12, 20480)
    assert config["experts_held"] == [0, 12] and config["vocab_size"] * 8 == 163840
    specs = model._survey(config).specs
    assert 4.16e9 < sum(int(np.prod(s[0])) for s in specs.values()) < 4.17e9  # 8.33 GB in bfloat16
    mixer = sum(int(np.prod(s[0])) for p, s in specs.items() if p.startswith("Layer_1/LatentAttention_0/"))
    assert 101.1e6 < mixer < 101.2e6
    per_token = model.flops_per_row(config) / 8192
    assert 3.9e9 < per_token < 4.05e9  # ISSUE 32: 1.16 + 5 x 0.505 + 0.29 GFLOP a token
    step = kernel_work.moe_experts(config, 2, 8192)
    assert step["calls"] == 5 and step["bytes"] > 5 * 1.05e9  # 1.06 GB of expert matrices a layer
    assert 0.70e12 < step["flops"] / 5 < 0.74e12  # 0.72 TFLOP a layer: compute-bound


def test_kernel_work_against_hand_counts():
    config = {"num_hidden_layers": 4, "first_k_dense_replace": 1, "hidden_size": 8,
              "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 6,
              "v_head_dim": 12, "num_experts_per_tok": 2, "experts_held": [0, 2],
              "router_width": 8, "moe_intermediate_size": 3}
    rows, tokens = 2, 10
    # 4 layers; half of 10 x 10 scores, 2 rows, 4 heads; the score product 22 wide, the other 12
    work = kernel_work.latent_attention(config, rows, tokens)
    assert work == {"calls": 4, "flops": 4 * 2 * 2 * 50 * 4 * (22 + 12),
                    # q 22 and the kv array's k_nope 16 and v 12 a head at 2 bytes, the rotary
                    # key's 6 once; o 12 a head at 2, as the kernel writes it
                    "bytes": 4 * 20 * (2 * (4 * 22 + 4 * (16 + 12) + 6) + 2 * 4 * 12)}
    # 3 layers route; 20 tokens x 2 choices x 2/8 held = 10 assignments; 3 matrices of 8 x 3
    work = kernel_work.moe_experts(config, rows, tokens)
    assert work == {"calls": 3, "flops": 3 * 2 * 10 * 3 * 24,
                    "bytes": 3 * (2 * 2 * 3 * 24 + 2 * 2 * 10 * 8)}
    assert work == kernel_work.moe_experts(
        dict(config, num_hidden_layers=3, first_k_dense_replace=0), rows, tokens)


def test_the_new_reader_finds_its_instructions_in_a_recorded_trace(monkeypatch):
    """The recorded v5e trace (six runs of ``jit_step``), as ``test_lm_cell.py`` reads it:
    the instruction the map places under ``LatentAttention_<i>/attention`` is that kernel's
    time; its share of the roofline is ``kernel_work.latent_attention``'s least time over it."""
    monkeypatch.setattr(tracing, "find_trace_file",
                        lambda log_dir: os.path.join(DATA, "trace_small.xplane.pb"))
    with open(os.path.join(DATA, "trace_small.spans.json")) as f:
        kept = json.load(f)
    summary = tracing.reduce_trace(os.path.join(DATA, "trace_small.xplane.pb"),
                                   kept["window"], kept["spans"])
    scopes = {"convert_element_type.3": "AXK1/LatentAttention_2/attention",
              "copy-start": "AXK1/LatentAttention_2/latent_proj/dot_general"}
    config = model.load_config("benchmarks/configs/axk1_ep16.json")
    view = {"observed": {"program.scopes": scopes, "rows_per_device_step": 2,
                         "tokens_per_row": 8192},
            "trace": summary, "config": config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    seconds, steps, _ = trace_kernel_share.kernel_seconds(view, "attention")
    assert steps == 6 and seconds == pytest.approx(6 * 142.735e-6, rel=0.01)
    work = kernel_work.latent_attention(config, 2, 8192)
    assert work["flops"] == 6 * 2 * 2 * (8192 * 8192 // 2) * 64 * 320  # 16.5 TFLOP a step
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == work["flops"] / 197e12  # compute-bound
    params = {"kernel": "attention", "work": "kernel_work.latent_attention"}
    assert trace_kernel_roofline.read(view, params) == pytest.approx(
        100 * least / (seconds / 6))
    # named in params, another work function over the same kernel's time
    older = dict(config, full_attention_interval=1, num_key_value_heads=64, head_dim=192)
    work = kernel_work.attention(older, 2, 8192)
    assert trace_kernel_roofline.read(
        dict(view, config=older), {"kernel": "attention", "work": "kernel_work.attention"}
    ) == pytest.approx(100 * max(work["flops"] / 197e12, work["bytes"] / 819e9) / (seconds / 6))
    # a program from before the kernel existed (the parent), no scope map, or no peaks:
    # nothing returned, nothing raised
    assert trace_kernel_roofline.read(
        view, {"kernel": "moe_experts", "work": "kernel_work.moe_experts"}) is None
    assert trace_kernel_share.read(view, {"kernel": "moe_experts"}) == 0.0
    assert trace_kernel_roofline.read(dict(view, peaks=None), params) is None
    view["observed"]["program.scopes"] = None
    assert trace_kernel_roofline.read(view, params) is None
    assert trace_kernel_share.read(view, {"kernel": "latent_proj"}) is None
