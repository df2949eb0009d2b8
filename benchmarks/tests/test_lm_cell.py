"""The language-model cell on the CPU at the traffic file's rehearsal widths: a run of
the cell end to end, ``correct`` false for the int8 control and for a program with the
decay left out and true for a sound run, the kernels' operations and bytes against hand
counts, and the kernel readers over the recorded v5e trace."""

import json
import os

import numpy as np
import pytest

from benchmarks import kernel_work, lm_weights, model, program_lm, run as bench_run, tracing
from benchmarks.comparers import logprob_rows
from benchmarks.readers import trace_kernel_roofline, trace_kernel_share

CELL = "qwen3next_score_stream"
DATA = os.path.join(os.path.dirname(__file__), "data")


def rehearsal_config():
    config = model.load_config("benchmarks/configs/qwen3next_80b_a3b_ep4.json")
    traffic = model.load_config("benchmarks/traffic/tokens_stream.json")
    config.update(traffic["rehearsal"]["config"])
    return config, traffic["rehearsal"]


def test_row_gaps_are_taken_about_the_reference_row_mean():
    ref = np.array([[-10.0, -12.0, -11.0, -11.0], [-9.0, -9.0, -13.0, -13.0]])
    gaps = logprob_rows.row_gaps(ref + np.array([[0.5, 0, 0, 0], [0, 0, 0, 0]]), ref)
    assert gaps == pytest.approx([0.5 / np.sqrt(2.0), 0.0])
    # a program that answers the same number everywhere is a whole reference-spread away
    flat = np.full_like(ref, -11.0)
    assert logprob_rows.row_gaps(flat, ref)[0] == pytest.approx(1.0)
    assert logprob_rows.row_gaps(np.where(ref < -10.5, np.nan, ref), ref)[0] == np.inf
    with pytest.raises(ValueError):
        logprob_rows.row_gaps(ref[:1], ref)


def test_compare_holds_each_number_to_its_own_limit_and_asks_for_spread():
    rng = np.random.default_rng(0)
    ref = -10.0 + 2.0 * rng.normal(size=(6, 200))
    limits = {"limits": {"centred_err_max": {"limit": 0.05}, "flatness_max": {"limit": 1.0}}}
    ok, compared = logprob_rows.compare_rows(ref + 0.02 * rng.normal(size=ref.shape), ref, limits)
    assert ok and compared["rows_compared"]["value"] == 6
    ok, _ = logprob_rows.compare_rows(ref + 0.2 * rng.normal(size=ref.shape), ref, limits)
    assert not ok
    flat_ref = -10.0 + 0.1 * rng.normal(size=(6, 200))  # a reference that tests nothing
    ok, compared = logprob_rows.compare_rows(flat_ref, flat_ref, limits)
    assert not ok and compared["flatness_max"]["value"] > 1.0
    ok, _ = logprob_rows.compare_rows(ref, ref, {"limits": {"centred_err_max": {"limit": None}}})
    assert not ok  # a number without a limit proves nothing
    with pytest.raises(KeyError):
        logprob_rows.compare_rows(ref, ref, {"limits": {"no_such_number": {"limit": 1.0}}})


def test_the_int8_control_and_a_missing_decay_are_not_correct_and_a_sound_program_is():
    config, traffic = rehearsal_config()
    seed = 2**31 + 5
    weights = lm_weights.make_weights(config, seed)
    tokens = lm_weights.token_rows(seed, 6, traffic["row_tokens"], config["vocab_size"], 1.0)
    reference = lm_weights.reference_outputs(config, weights, tokens)
    control = lm_weights.reference_outputs(config, weights, tokens, quant="int8")
    ok, compared = logprob_rows.compare_rows(control, reference, config["correct"])
    assert not ok, compared
    no_decay = lm_weights.reference_outputs(config, weights, tokens, use_decay=False)
    ok, compared = logprob_rows.compare_rows(no_decay, reference, config["correct"])
    assert not ok, compared
    program = program_lm.model_function(config, weights, traffic["row_tokens"])
    ok, compared = logprob_rows.compare_rows(np.asarray(program(tokens)), reference,
                                             config["correct"])
    assert ok, compared


def test_the_same_seed_gives_the_same_weights_and_rows_and_a_large_seed_is_taken():
    config, _ = rehearsal_config()
    a, b = lm_weights.make_weights(config, 2**31 + 99), lm_weights.make_weights(config, 2**31 + 99)
    assert all(np.array_equal(np.asarray(a[k], np.float32), np.asarray(b[k], np.float32)) for k in a)
    c = lm_weights.make_weights(config, 2**31 + 100)
    assert not np.array_equal(np.asarray(a["head"], np.float32), np.asarray(c["head"], np.float32))
    assert str(a["head"].dtype) == "bfloat16" and str(a["final_norm"].dtype) == "float32"
    rows = lm_weights.token_rows(2**31 + 99, 8, 4096, 37984, 1.0)
    assert rows.dtype == np.int32 and rows.min() >= 0 and rows.max() < 37984
    assert np.array_equal(rows, lm_weights.token_rows(2**31 + 99, 8, 4096, 37984, 1.0))
    assert len({r.tobytes() for r in rows}) == 8
    # a Zipf law of exponent 1: id 0 takes 1 / H(37984) = 9% of the draws
    assert 0.07 < (rows == 0).mean() < 0.11


def test_a_rehearsal_run_of_the_cell(capsys):
    assert bench_run.main(["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
                           "--trace", "1", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] is True
    assert all(k.startswith("cpu_rehearsal.") for k in result["metrics"])
    for name in ("engine.outside_runner_share.lm", "runner.transfer_wait_share.lm",
                 "moe.held_assignment_share.lm", "moe.expert_load_max_over_mean.lm"):
        assert "cpu_rehearsal." + name in result["metrics"], sorted(result["metrics"])
    share = result["metrics"]["cpu_rehearsal.moe.held_assignment_share.lm"]["value"]
    assert 0.0 < share < 100.0
    assert result["metrics"]["cpu_rehearsal.moe.expert_load_max_over_mean.lm"]["value"] >= 1.0
    # 2 partitions of 5 rows: first and last row and the rows round the first batch cut
    assert result["compared"]["rows_compared"]["value"] in (8, 16)
    passes = [line for line in out if line.startswith("pass ")]
    assert any("(traced)" in line for line in passes) and "(traced)" not in passes[-1]


def test_kernel_work_against_hand_counts():
    config = {"num_hidden_layers": 4, "full_attention_interval": 4, "hidden_size": 8,
              "linear_num_value_heads": 2, "linear_key_head_dim": 4, "linear_value_head_dim": 6,
              "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
              "num_experts_per_tok": 2, "experts_held": [0, 2], "router_width": 8,
              "moe_intermediate_size": 3}
    rows, tokens = 2, 10
    # 3 delta-rule layers; 2 x 10 x 2 = 40 positions-and-heads; 3 products of 4 x 6
    work = kernel_work.gdn_scan(config, rows, tokens)
    assert work == {"calls": 3, "flops": 3 * (3 * 2 * 40 * 24), "bytes": 3 * 4 * 40 * (8 + 12 + 2)}
    # 1 full layer; half of 10 x 10 scores, 2 products of 16, 4 heads, 2 rows; q, k, v in
    # and o out at the configuration's 2 bytes, whatever type the program's kernel writes
    work = kernel_work.attention(config, rows, tokens)
    assert work == {"calls": 1, "flops": 4 * 2 * 50 * 16 * 4,
                    "bytes": 2 * 10 * 16 * (2 * (4 + 4) + 2 * 4)}
    # 4 layers; 20 tokens x 2 choices x 2/8 held = 10 assignments; 3 matrices of 8 x 3
    work = kernel_work.moe_experts(config, rows, tokens)
    assert work == {"calls": 4, "flops": 4 * 2 * 10 * 3 * 24,
                    "bytes": 4 * (2 * 2 * 3 * 24 + 2 * 2 * 10 * 8)}
    # every roofline metric file names its work function, and it is one of kernel_work's
    files = [model.load_config(os.path.join(os.path.dirname(DATA), "..", "metrics", f))
             for f in os.listdir(os.path.join(os.path.dirname(DATA), "..", "metrics"))]
    works = {f["params"]["work"] for f in files if f["reader"] == "trace_kernel_roofline"}
    assert works == {"kernel_work.gdn_scan", "kernel_work.moe_experts", "kernel_work.attention",
                     "kernel_work.latent_attention"}


def test_the_published_widths_give_the_issues_counts():
    config = model.load_config("benchmarks/configs/qwen3next_80b_a3b_ep4.json")
    per_token = model.flops_per_row(config) / 8192
    assert 0.95e9 < per_token < 1.10e9  # ISSUE 28 reckoned 1.015 GFLOP a token
    step = kernel_work.moe_experts(config, 2, 8192)
    assert step["bytes"] > 8 * 805e6  # 805 MB of expert matrices a layer, 8 layers


def test_kernel_readers_find_their_instructions_in_a_recorded_trace(monkeypatch):
    """The recorded v5e trace (six runs of ``jit_step``): the instruction the map places
    under a kernel's name is that kernel's time, and nothing else is."""
    monkeypatch.setattr(tracing, "find_trace_file",
                        lambda log_dir: os.path.join(DATA, "trace_small.xplane.pb"))
    with open(os.path.join(DATA, "trace_small.spans.json")) as f:
        kept = json.load(f)
    summary = tracing.reduce_trace(os.path.join(DATA, "trace_small.xplane.pb"),
                                   kept["window"], kept["spans"])
    scopes = {"convert_element_type.3": "Net/GatedDeltaNet_0/gdn_scan", "copy-start": "Net/other"}
    config = model.load_config("benchmarks/configs/qwen3next_80b_a3b_ep4.json")
    view = {"observed": {"program.scopes": scopes, "rows_per_device_step": 2,
                         "tokens_per_row": 8192},
            "trace": summary, "config": config,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    seconds, steps, step_seconds = trace_kernel_share.kernel_seconds(view, "gdn_scan")
    assert steps == 6 and step_seconds == pytest.approx(6 * 0.3684e-3, rel=0.01)
    assert seconds == pytest.approx(6 * 142.735e-6, rel=0.01)  # the convert of each run
    assert trace_kernel_share.read(view, {"kernel": "gdn_scan"}) == pytest.approx(
        100 * seconds / step_seconds)
    assert trace_kernel_share.read(view, {"kernel": "attention"}) == 0.0
    work = kernel_work.gdn_scan(config, 2, 8192)
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    gdn = {"kernel": "gdn_scan", "work": "kernel_work.gdn_scan"}
    assert trace_kernel_roofline.read(view, gdn) == pytest.approx(100 * least / (seconds / 6))
    # nothing to read: nothing returned, nothing raised
    attention = {"kernel": "attention", "work": "kernel_work.attention"}
    assert trace_kernel_roofline.read(view, attention) is None
    view["observed"]["program.scopes"] = None
    assert trace_kernel_share.read(view, {"kernel": "gdn_scan"}) is None
    assert trace_kernel_roofline.read(view, gdn) is None
