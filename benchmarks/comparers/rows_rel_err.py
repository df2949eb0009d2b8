"""Comparer ``rows_rel_err``: what the timed path answered for the sampled rows
against the plain reference over the same rows, row by row.

A configuration's ``correct`` block names its comparer and gives it its limits:
``{"comparer": "rows_rel_err", "limits": {"rel_err_max": {"limit": ...}, ...}}``.
``run.py`` calls ``compare(run, outcome)`` once the window has closed, the peak
has been read and the program's state is dropped. This one reads
``outcome.evidence["inputs"]`` (rows as the timed path was given them) and
``["outputs"]`` (what it answered, row for row)."""

from __future__ import annotations

import numpy as np

from benchmarks import model


def row_gaps(answers: np.ndarray, reference: np.ndarray) -> np.ndarray:
    """For each row, the distance between answer and reference as a share of the
    reference's length. A row that is missing, NaN or infinite reads infinity."""
    answers = np.asarray(answers, np.float64)
    reference = np.asarray(reference, np.float64)
    if answers.shape != reference.shape:
        raise ValueError(f"answers {answers.shape} against reference {reference.shape}")
    gaps = np.linalg.norm(answers - reference, axis=1) / np.linalg.norm(reference, axis=1)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def compare_rows(answers: np.ndarray, reference: np.ndarray, spec: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` for the numbers that ``spec`` limits:
    ``rel_err_max`` is the widest row gap, ``rel_err_p50`` the median one."""
    limits = spec["limits"]
    gaps = row_gaps(answers, reference)
    readings = {"rel_err_max": float(np.max(gaps)), "rel_err_p50": float(np.median(gaps))}
    unknown = set(limits) - set(readings)
    if unknown:
        raise KeyError(f"no such compared number: {sorted(unknown)}")
    compared = {name: {"value": readings[name], "limit": limit["limit"]}
                for name, limit in limits.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"] for c in compared.values())
    compared["rows_compared"] = {"value": int(len(gaps)), "limit": None}
    return correct, compared


def compare(run, outcome) -> tuple[bool, dict]:
    """The weights are made again from the seed: the reference takes nothing that
    has been through the program's hands."""
    config = run.config
    reference = model.reference_outputs(config, model.make_weights(config, run.seed),
                                        outcome.evidence["inputs"])
    return compare_rows(outcome.evidence["outputs"], reference, config["correct"])
