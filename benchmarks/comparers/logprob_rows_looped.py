"""Comparer ``logprob_rows_looped``: ``logprob_rows`` for a looped model, which has no router
and a second output of its own. The log-probabilities the timed path answered for the
sampled rows are held to the plain reference's exactly as ``logprob_rows`` holds them (its
``compare_rows``: the same three numbers, each row's gap about the reference's row mean), and
one number is added: ``exit_pdf_err_max``, the largest absolute gap between a compared row's
exit distribution (``exit_pdf``, ``total_ut_steps`` numbers that sum to 1) and the
reference's, under a limit of its own.

``compare(run, outcome)`` reads ``outcome.evidence["inputs"]`` (token rows as the timed path
was given them), ``["outputs"]`` (its log-probabilities, row for row) and ``["exit_pdf"]``, and
makes the weights again from the seed: the reference takes nothing that has been through the
program's hands. ``reference_outputs`` is here and not in ``lm_weights`` because that file asks
every reference for a ``routing`` output."""

from __future__ import annotations

import functools
import json

import jax
import numpy as np

from benchmarks import lm_weights, model
from benchmarks.comparers import logprob_rows
from benchmarks.reference.nn import Net


@functools.lru_cache(maxsize=4)
def _reference_fn(config_json: str, quant, broken: tuple):
    config = json.loads(config_json)
    forward = model._forward(config)

    @jax.jit
    def apply(params, tokens):
        out = forward(Net(params=params, quant=quant), tokens, config, **dict(broken))
        return out["logprobs"], out["exit_pdf"]

    return apply


def reference_outputs(config: dict, weights: dict, tokens: np.ndarray, quant=None,
                      block: int = 2, **broken) -> tuple:
    """``(logprobs [rows, T - 1], exit_pdf [rows, passes])`` of the reference (with ``quant``,
    the control; with a keyword of the reference's own for a broken program, that program)
    over ``tokens``, ``block`` rows at a time so that it fits the chip."""
    apply = _reference_fn(json.dumps(config, sort_keys=True), quant, tuple(sorted(broken.items())))
    logprobs, pdfs = [], []
    for lo in range(0, len(tokens), block):
        chunk = tokens[lo:lo + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, np.repeat(chunk[-1:], pad, axis=0)])
        answers, pdf = apply(weights, chunk)
        logprobs.append(np.asarray(answers)[:block - pad])
        pdfs.append(np.asarray(pdf)[:block - pad])
    return np.concatenate(logprobs), np.concatenate(pdfs)


def compare_rows(answers, exit_pdf, reference, reference_pdf, spec: dict) -> tuple[bool, dict]:
    """``logprob_rows.compare_rows`` over the log-probabilities, and ``exit_pdf_err_max``."""
    limits = dict(spec["limits"])
    pdf_limit = limits.pop("exit_pdf_err_max")["limit"]
    correct, compared = logprob_rows.compare_rows(answers, reference, dict(spec, limits=limits))
    exit_pdf, reference_pdf = np.asarray(exit_pdf, np.float64), np.asarray(reference_pdf, np.float64)
    if exit_pdf.shape != reference_pdf.shape:
        raise ValueError(f"exit_pdf {exit_pdf.shape} against reference {reference_pdf.shape}")
    gap = np.abs(exit_pdf - reference_pdf)
    value = float(gap.max()) if np.isfinite(gap).all() else float("inf")
    compared["exit_pdf_err_max"] = {"value": value, "limit": pdf_limit}
    return correct and pdf_limit is not None and value <= pdf_limit, compared


def compare(run, outcome) -> tuple[bool, dict]:
    config = run.config
    reference, reference_pdf = reference_outputs(
        config, lm_weights.make_weights(config, run.seed), outcome.evidence["inputs"])
    return compare_rows(outcome.evidence["outputs"], outcome.evidence["exit_pdf"],
                        reference, reference_pdf, config["correct"])
