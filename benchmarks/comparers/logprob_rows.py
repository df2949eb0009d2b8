"""Comparer ``logprob_rows``: the log-probabilities the timed path answered for the
sampled rows against the plain reference over the same rows, row by row, about the
reference's row mean.

Log-probabilities over a vocabulary of 37,984 sit near -10.5, so the distance between
answer and reference as a share of the reference's length (``rows_rel_err``) is blind:
a program that answered ``-log V`` everywhere would pass. Here a row's gap is that
distance as a share of the distance of the reference from its own mean, which is 0 for
a program that knows nothing of the row. ``flatness_max`` (1 over the smallest standard
deviation of a reference row) holds the configuration's seeded weights to a spread that
makes the share mean something. ``rows_routed_apart`` is told and not limited: the share
of the compared rows in which the program's routing counts differ from the reference's.

Where the configuration's ``program`` block names other outputs of the program
(``program_lm.py``), the reference's outputs of those names are taken beside: an
``exit_pdf`` (a looped model's exit distribution, ``total_ut_steps`` numbers a row that sum
to 1) is held by ``exit_pdf_err_max``, the largest absolute gap of a compared row's from the
reference's, where the limits name it; a ``routing`` output is told by ``rows_routed_apart``.

``compare(run, outcome)`` reads ``outcome.evidence["inputs"]`` (token rows as the timed
path was given them) and, row for row, what it answered under each output's name, and
makes the weights again from the seed: the reference takes nothing that has been through
the program's hands."""

from __future__ import annotations

import numpy as np

from benchmarks import lm_weights


def row_gaps(answers: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """For each row, ``|answer - reference| / |reference - mean(reference)|``. A row
    that is missing, NaN or infinite reads infinity."""
    answers = np.asarray(answers, np.float64)
    reference = np.asarray(reference, np.float64)
    if answers.shape != reference.shape:
        raise ValueError(f"answers {answers.shape} against reference {reference.shape}")
    centred = reference - reference.mean(axis=1, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        gaps = np.linalg.norm(answers - reference, axis=1) / np.linalg.norm(centred, axis=1)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def compare_rows(answers: np.ndarray, reference: np.ndarray, spec: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for the numbers that ``spec`` limits:
    ``centred_err_max`` the widest row gap, ``centred_err_p50`` the median one,
    ``flatness_max`` 1 over the smallest standard deviation of a reference row."""
    limits = spec["limits"]
    gaps = row_gaps(answers, reference)
    spread = np.asarray(reference, np.float64).std(axis=1)
    readings = {"centred_err_max": float(np.max(gaps)),
                "centred_err_p50": float(np.median(gaps)),
                "flatness_max": float(1.0 / spread.min()) if spread.min() > 0 else float("inf")}
    unknown = set(limits) - set(readings)
    if unknown:
        raise KeyError(f"no such compared number: {sorted(unknown)}")
    compared = {name: {"value": readings[name], "limit": limit["limit"]}
                for name, limit in limits.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"] for c in compared.values())
    compared["rows_compared"] = {"value": int(len(gaps)), "limit": None}
    return correct, compared


def rows_routed_apart(program_counts: np.ndarray, reference_counts: np.ndarray) -> float:
    """Share of the rows in which some held expert of some layer received another number
    of assignments from the program than from the reference (``[rows, layers, held]``):
    a choice at the tenth expert that rounding flipped, seen from outside (a flip between
    two experts held elsewhere does not show, so the share is a floor). The program's
    rows may be its ``routing`` output as it is, a layer's held total before its experts'
    counts."""
    reference_counts = np.asarray(reference_counts)
    rows, layers, held = reference_counts.shape
    program_counts = np.asarray(program_counts).reshape(rows, layers, -1)[:, :, -held:]
    return float((program_counts != reference_counts).any(axis=(1, 2)).mean())


def exit_pdf_err_max(exit_pdf: np.ndarray, reference_pdf: np.ndarray) -> float:
    """The largest absolute gap of a row's exit distribution from the reference's."""
    exit_pdf, reference_pdf = np.asarray(exit_pdf, np.float64), np.asarray(reference_pdf)
    if exit_pdf.shape != reference_pdf.shape:
        raise ValueError(f"exit_pdf {exit_pdf.shape} against reference {reference_pdf.shape}")
    gap = np.abs(exit_pdf - reference_pdf)
    return float(gap.max()) if np.isfinite(gap).all() else float("inf")


def compare_outputs(answers: dict, reference: dict, head: str, spec: dict) -> tuple[bool, dict]:
    """``compare_rows`` over the ``head`` output of ``answers`` and ``reference`` (each
    ``{output name: rows}``), ``exit_pdf_err_max`` where ``spec`` limits it, and
    ``rows_routed_apart`` where both hold a ``routing`` output (told, not limited:
    rounding flips near-ties). Every other output of the reference (the configuration's
    ``program`` block names them) has to be one of these: ``exit_pdf`` with its limit,
    ``routing``. Any other raises, and so does ``exit_pdf`` with no limit."""
    limits = dict(spec["limits"])
    pdf_limit = limits.pop("exit_pdf_err_max", None)
    if "exit_pdf" in reference and pdf_limit is None:
        raise ValueError("the configuration names the output exit_pdf and no exit_pdf_err_max")
    unchecked = set(reference) - {head, "exit_pdf", "routing"}
    if unchecked:
        raise ValueError(f"outputs neither limited nor told: {sorted(unchecked)}")
    correct, compared = compare_rows(answers[head], reference[head], dict(spec, limits=limits))
    if pdf_limit is not None:
        value = exit_pdf_err_max(answers["exit_pdf"], reference["exit_pdf"])
        compared["exit_pdf_err_max"] = {"value": value, "limit": pdf_limit["limit"]}
        correct = correct and pdf_limit["limit"] is not None and value <= pdf_limit["limit"]
    if "routing" in answers and "routing" in reference:
        compared["rows_routed_apart"] = {
            "value": rows_routed_apart(answers["routing"], reference["routing"]), "limit": None}
    return correct, compared


def reference_of(config: dict, weights: dict, tokens: np.ndarray, **kwargs) -> dict:
    """``{output name: rows}`` of the reference over ``tokens``: its head and the other
    outputs that the configuration's ``program`` block names (``kwargs``: the control's
    ``quant``, a fault's keywords)."""
    others = list(config["program"].get("outputs", {}))
    found = lm_weights.reference_outputs(config, weights, tokens, outputs=others, **kwargs)
    return dict(zip([config["head"], *others], found))


def compare(run, outcome) -> tuple[bool, dict]:
    config = run.config
    weights = lm_weights.make_weights(config, run.seed)
    reference = reference_of(config, weights, outcome.evidence["inputs"])
    return compare_outputs(outcome.evidence, reference, config["head"], config["correct"])
