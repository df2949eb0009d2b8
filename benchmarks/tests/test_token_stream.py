"""The one driver of the language-model cells (``drivers/token_stream.py``), the one reference
call (``lm_weights.reference_outputs``) and the one comparer (``comparers/logprob_rows``) on
the CPU at the traffic files' rehearsal widths: each cell compares and counts to the last
digit what it did under the three drivers it replaced; a configuration whose program has no
output beside its log-probabilities runs through them with nothing of its own; and the
attention kernels' rooflines stay set by their operations."""

import glob
import importlib
import json
import time

import jax
import numpy as np
import pytest

from benchmarks import harness, kernel_work, lm_weights, model, program_lm, run as bench_run
from benchmarks.comparers.logprob_rows import compare_outputs, reference_of

# cells 4, 5 and 6 at rehearsal size, seed 2**31 + 11, a window of one pass (10 rows), as
# the three drivers before the fold (token_stream, token_stream_routed, token_stream_looped)
# and their comparers (logprob_rows, logprob_rows_looped) gave them
BEFORE = {
    "qwen3next_score_stream": (
        {"centred_err_max": 0.10269259744907266, "centred_err_p50": 0.011404802617996803,
         "flatness_max": 0.8006367216029111, "rows_compared": 8, "rows_routed_apart": 0.5},
        {"moe.assignments": 3840.0, "moe.assignments_held": 1573.0, "moe.expert_load_max": 212.0,
         "moe.expert_load_max_over_mean": 1.6172917991099809}),
    "axk1_score_stream": (
        {"centred_err_max": 0.16903617708016475, "centred_err_p50": 0.01188041324729878,
         "flatness_max": 0.5607818178832505, "rows_compared": 8, "rows_routed_apart": 0.5},
        {"moe.assignments": 2880.0, "moe.assignments_held": 1325.0, "moe.expert_load_max": 223.0,
         "moe.expert_load_max_over_mean": 1.5147169811320753}),
    "ouro_score_stream": (
        {"centred_err_max": 0.011301493412935846, "centred_err_p50": 0.008035526634079734,
         "flatness_max": 0.5860360770009002, "rows_compared": 4,
         "exit_pdf_err_max": 0.0007359683513641357},
        {"loop.rows": 10.0, "loop.exit_step_mean": 2.2660858273506164}),
}


class _ProfilerOff:
    """A tracer that never starts: with ``--trace 1`` and no seconds, the window is exactly
    one untraced pass, so the counters do not depend on the clock."""
    active = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _one_pass(name: str):
    bench = model.load_config("BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    run = harness.Run(cell=cell, config=model.load_config(entry["file"]),
                      traffic=model.load_config(f"benchmarks/traffic/{cell['traffic']}.json"),
                      seed=2**31 + 11, seconds=0.0, trace=True, rehearsal=True,
                      started=time.perf_counter(), devices=jax.devices()[:1],
                      tracer=_ProfilerOff())
    driver = importlib.import_module(f"benchmarks.drivers.{run.traffic['driver']}")
    outcome = driver.run(run)
    outcome.release()
    comparer = importlib.import_module(f"benchmarks.comparers.{run.config['correct']['comparer']}")
    return run, outcome, comparer.compare(run, outcome)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_each_cell_compares_and_counts_as_before_the_fold(name):
    run, outcome, (correct, compared) = _one_pass(name)
    assert run.traffic["driver"] == "token_stream" and run.config["correct"]["comparer"] == "logprob_rows"
    assert correct and outcome.attempted == 10
    numbers, counters = BEFORE[name]
    assert {k: c["value"] for k, c in compared.items()} == numbers
    assert {k: v for k, v in outcome.observed.items() if k.startswith(("moe.", "loop."))} == counters


def _dense(data: dict, path: str) -> dict:
    """The Ouro files with the program's second output named nowhere: no ``outputs`` in the
    configuration's ``program`` block, no ``exit_pdf_err_max`` in the rehearsal's limits."""
    if path == "benchmarks/configs/ouro_2p6b.json":
        del data["program"]["outputs"]
    if path == "benchmarks/traffic/tokens_stream_4k.json":
        del data["rehearsal"]["config"]["correct"]["limits"]["exit_pdf_err_max"]
    return data


def test_a_configuration_with_no_second_output_needs_nothing_of_its_own(monkeypatch, capsys):
    load = bench_run._load
    monkeypatch.setattr(bench_run, "_load", lambda path: _dense(load(path), path))
    assert bench_run.main(["--workload", "ouro_score_stream", "--seed", str(2**31 + 13),
                           "--seconds", "0.5", "--trace", "0", "--rehearsal", "1"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    result = json.loads(out[-1])
    assert result["correct"] is True, result["compared"]
    assert set(result["compared"]) == {"centred_err_max", "centred_err_p50", "flatness_max",
                                       "rows_compared", "answers_lost"}
    window = next(line for line in out if line.startswith("window: "))
    assert "; " not in window  # nothing recorded beside the rate
    # the int8 control through the same reference call and comparer is not correct
    files = ("benchmarks/configs/ouro_2p6b.json", "benchmarks/traffic/tokens_stream_4k.json")
    config, traffic = (_dense(model.load_config(path), path) for path in files)
    traffic = traffic["rehearsal"]
    config.update(traffic["config"])
    weights = lm_weights.make_weights(config, 2**31 + 13)
    tokens = lm_weights.token_rows(2**31 + 13, 4, traffic["row_tokens"], config["vocab_size"], 1.0)
    reference = reference_of(config, weights, tokens)
    assert set(reference) == {"logprobs"}
    ok, compared = compare_outputs(reference_of(config, weights, tokens, quant="int8"), reference,
                                   "logprobs", config["correct"])
    assert not ok and compared["centred_err_max"]["value"] > compared["centred_err_max"]["limit"]


def test_every_configuration_file_is_built_from_its_own_program_block():
    """``program_lm._NAMING`` serves only a block that names its module alone, as the program's
    own tests write one; no file under ``configs/`` is such a block."""
    for path in sorted(glob.glob("benchmarks/configs/*.json")):
        block = model.load_config(path)["program"]
        if "module" in block:
            own = {"blocks": block.get("blocks", {}), "keys": block.get("keys", {})}
            assert set(block) != {"module"} and program_lm._naming(block) == own, path


@pytest.mark.parametrize("extra, limits", [
    ("exit_pdf", {}), ("hidden_states", {"exit_pdf_err_max": {"limit": 0.1}})])
def test_an_output_the_comparer_neither_limits_nor_tells_raises(extra, limits):
    rows = np.linspace(-12.0, -8.0, 40).reshape(2, 20)
    outputs = {"logprobs": rows, extra: np.full((2, 4), 0.25)}
    spec = {"limits": {"centred_err_max": {"limit": 0.1}, "centred_err_p50": {"limit": 0.1},
                       "flatness_max": {"limit": 1.0}, **limits}}
    with pytest.raises(ValueError, match="exit_pdf_err_max" if extra == "exit_pdf" else extra):
        compare_outputs(outputs, outputs, "logprobs", spec)


@pytest.mark.parametrize("file, work, ms_a_call", [
    ("qwen3next_80b_a3b_ep4", kernel_work.attention, 5.58),
    ("axk1_ep16", kernel_work.latent_attention, 13.953),
    ("ouro_2p6b", kernel_work.attention, 0.698)])
def test_each_attention_roofline_is_set_by_its_operations(file, work, ms_a_call):
    """At the published shapes of a step (2 rows), a call's operations take several times
    its bytes' time, so the bytes counted (o out at 2 bytes, though cell 4's kernel writes
    float32) move no reading."""
    config = model.load_config(f"benchmarks/configs/{file}.json")
    tokens = config["input_shape"][0]
    step = work(config, 2, tokens)
    flops_ms = 1e3 * step["flops"] / step["calls"] / 197e12
    bytes_ms = 1e3 * step["bytes"] / step["calls"] / 819e9
    assert flops_ms == pytest.approx(ms_a_call, rel=1e-3)
    assert 0.1 < bytes_ms < 2.0 and flops_ms > 3 * bytes_ms
