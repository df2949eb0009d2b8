"""THE error classification: ``Transient`` (a re-run can plausibly fix it)
vs ``Permanent`` (the same attempt fails the same way again).

Before this module the classification lived in ``data/engine.py`` as
``default_retryable_exceptions()`` + ``is_deterministic_jax_error()``
and applied only to partition retry; the serve dispatcher had no
retry at all, so a single transient dispatch failure failed every
request a coalesced micro-batch carried. Centralizing the split here
gives every retry decision in the tree — the engine's partition
re-runs, the serve dispatcher's micro-batch re-dispatch, circuit-
breaker failure counting — ONE classifier, so "what is worth retrying"
cannot drift between layers.

The split is typed first, heuristic second:

* anything raising (or wrapping itself in) :class:`TransientError` /
  :class:`PermanentError` is classified by its type — the fault
  harness (:mod:`sparkdl_tpu.resilience.faults`) and
  :class:`~sparkdl_tpu.resilience.policy.RetryBudgetExhausted` use
  these markers;
* ``OSError`` stays transient (disk and Arrow IO re-reads cleanly);
* jax/PJRT runtime errors are transient UNLESS their absl status code
  is deterministic (``INVALID_ARGUMENT``, a genuine
  ``RESOURCE_EXHAUSTED`` allocation failure, ...) — re-running a
  program whose shapes are wrong just triples time-to-failure;
* everything else (user errors: bad column names, shape mismatches)
  is permanent and propagates on first failure.
"""

from __future__ import annotations

from typing import Tuple


class TransientError(RuntimeError):
    """Marker base: a failure a bounded, backed-off re-attempt can
    plausibly fix (dropped connection, preempted device, injected
    transient fault). ``is_transient()`` returns True for subclasses
    without any message sniffing."""


class PermanentError(RuntimeError):
    """Marker base: a failure that will recur deterministically —
    retrying it burns time and retry budget for nothing.
    ``is_transient()`` returns False for subclasses even when they
    also inherit from an otherwise-retryable family."""


def default_retryable_exceptions() -> Tuple[type, ...]:
    """Exception families a re-run can plausibly fix.

    ``OSError`` covers disk and Arrow IO. The jax runtime-error family
    covers transient device failures — a lost device connection
    mid-partition, a preempted device — which re-run cleanly because sources re-load from disk and
    stages are pure. jax errors carrying a DETERMINISTIC status code
    (INVALID_ARGUMENT, a genuine RESOURCE_EXHAUSTED allocation failure,
    ...) are filtered out by :func:`is_deterministic_jax_error` even
    though the class is listed here. :class:`TransientError` marks
    explicitly-transient failures (injected faults included).
    Python-level user errors (bad column names, trace-time shape
    mismatches) are never retried.
    """
    excs = [OSError, TransientError]
    try:
        from jax.errors import JaxRuntimeError
        excs.append(JaxRuntimeError)
    except ImportError:  # pragma: no cover - jax is a hard dep in env
        pass
    return tuple(excs)


# Status codes that mean "this exact program will fail this exact way
# again" — re-running the partition cannot help, so time-to-failure must
# not triple and the retry warning must not suggest transience.
# (RESOURCE_EXHAUSTED: a program whose allocations exceed HBM fails
# deterministically; transient allocator races surface as INTERNAL or
# UNAVAILABLE in PJRT.)
_DETERMINISTIC_JAX_STATUSES = (
    "INVALID_ARGUMENT", "NOT_FOUND", "ALREADY_EXISTS", "PERMISSION_DENIED",
    "FAILED_PRECONDITION", "OUT_OF_RANGE", "UNIMPLEMENTED",
    "RESOURCE_EXHAUSTED", "UNAUTHENTICATED",
)


def is_deterministic_jax_error(exc: BaseException) -> bool:
    """True when a jax/PJRT runtime error carries a status code that a
    re-run cannot fix. XlaRuntimeError IS JaxRuntimeError; the absl
    status name is searched as a ``NAME:`` token in the message's first
    line rather than only at position 0 — wrapping layers commonly
    prefix context ("Execution failed: INVALID_ARGUMENT: ...")."""
    try:
        from jax.errors import JaxRuntimeError
    except ImportError:  # pragma: no cover
        return False
    if not isinstance(exc, JaxRuntimeError):
        return False
    msg = str(exc).lstrip()
    first_line = msg.splitlines()[0] if msg else ""
    return any(f"{s}:" in first_line
               for s in _DETERMINISTIC_JAX_STATUSES)


def is_transient(exc: BaseException) -> bool:
    """THE shared classifier: may a bounded re-attempt fix ``exc``?
    Typed markers win (``PermanentError`` beats any inherited
    retryable family), then the default retryable families filtered
    by the deterministic-jax-status check."""
    if isinstance(exc, PermanentError):
        return False
    if isinstance(exc, TransientError):
        return True
    if not isinstance(exc, default_retryable_exceptions()):
        return False
    return not is_deterministic_jax_error(exc)


def classify(exc: BaseException) -> str:
    """``"transient"`` or ``"permanent"`` — the readable form of
    :func:`is_transient` for logs, bundles, and tests."""
    return "transient" if is_transient(exc) else "permanent"
