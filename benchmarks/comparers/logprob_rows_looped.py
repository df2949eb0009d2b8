"""Not a comparer: ``logprob_rows``'s reference and comparison of a looped model's two
outputs, under the name and in the form that ``tests/test_ouro.py`` and scripts under
``tools/chip_calls/`` import. No configuration names it; ``logprob_rows`` compares every
language-model configuration."""

from benchmarks import lm_weights
from benchmarks.comparers import logprob_rows


def reference_outputs(config: dict, weights: dict, tokens, quant=None, block: int = 2,
                      **broken) -> tuple:
    """``(logprobs, exit_pdf)`` of ``lm_weights.reference_outputs``."""
    return lm_weights.reference_outputs(config, weights, tokens, quant=quant, block=block,
                                        outputs=["exit_pdf"], **broken)


def compare_rows(answers, exit_pdf, reference, reference_pdf, spec: dict) -> tuple[bool, dict]:
    """``logprob_rows.compare_outputs`` over the two outputs."""
    return logprob_rows.compare_outputs({"logprobs": answers, "exit_pdf": exit_pdf},
                                        {"logprobs": reference, "exit_pdf": reference_pdf},
                                        "logprobs", spec)
