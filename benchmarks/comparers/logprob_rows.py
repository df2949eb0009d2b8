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

``compare(run, outcome)`` reads ``outcome.evidence["inputs"]`` (token rows as the timed
path was given them) and ``["outputs"]`` (its log-probabilities, row for row), and makes
the weights again from the seed: the reference takes nothing that has been through the
program's hands."""

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
    of assignments from the program than from the reference: a choice at the tenth
    expert that rounding flipped, seen from outside (a flip between two experts held
    elsewhere does not show, so the share is a floor)."""
    apart = (np.asarray(program_counts) != np.asarray(reference_counts)).any(axis=(1, 2))
    return float(apart.mean())


def compare(run, outcome) -> tuple[bool, dict]:
    config = run.config
    reference, routed = lm_weights.reference_outputs(
        config, lm_weights.make_weights(config, run.seed), outcome.evidence["inputs"],
        routing=True)
    correct, compared = compare_rows(outcome.evidence["outputs"], reference, config["correct"])
    if "routing" in outcome.evidence:  # told, not limited: rounding flips near-ties
        compared["rows_routed_apart"] = {
            "value": rows_routed_apart(outcome.evidence["routing"], routed), "limit": None}
    return correct, compared
