"""Resilience: the error classification, fault injection, retry policy, and
circuit breaking the rest of the stack survives failure with.

The stack can *see* failure (obs/watchdog, obs/flight, obs/slo) and
*statically forbid* whole classes of it (sparkdl_tpu/analysis); this
package is how it *survives* it (docs/RESILIENCE.md):

* :mod:`sparkdl_tpu.resilience.errors` — THE typed ``Transient`` vs
  ``Permanent`` split: one classifier (``is_transient``) every retry
  decision in the tree shares, migrated from the engine's ad-hoc
  ``default_retryable_exceptions`` + jax-status sniffing;
* :mod:`sparkdl_tpu.resilience.faults` — a deterministic
  fault-injection harness (``SPARKDL_TPU_FAULTS=<site>:<kind>:<rate>
  [:seed]``, or programmatic :func:`~sparkdl_tpu.resilience.faults
  .inject`) with named sites threaded through the hot paths: engine
  source load / stage apply, runner device_put / drain, collective
  launch, serve dispatch, model-fetch I/O. Every armed injection
  counts in the ``faults.*`` registry family and rides flight bundles
  and ``/statusz``; disarmed every site is one armed-check (the
  tracer's shared no-op regime, overhead-pinned);
* :mod:`sparkdl_tpu.resilience.policy` — one shared
  :class:`RetryPolicy` (bounded attempts, exponential backoff with
  deterministic jitter, a retry BUDGET so a failing dependency cannot
  amplify offered load) that ``LocalEngine``'s partition retry runs on
  and the serve dispatcher adopts for micro-batch re-dispatch; plus
  the per-``ModelSession`` :class:`CircuitBreaker`
  (closed → open → half-open with probe dispatches) that makes a
  persistently broken model shed fast-and-typed instead of burning
  every client's deadline.
"""

from sparkdl_tpu.resilience.errors import (
    PermanentError,
    TransientError,
    classify,
    default_retryable_exceptions,
    is_deterministic_jax_error,
    is_transient,
)
from sparkdl_tpu.resilience.faults import (
    FaultSpecError,
    InjectedFault,
    InjectedPermanentFault,
    SITES,
    disarm,
    inject,
    maybe_fail,
)
from sparkdl_tpu.resilience.faults import state as faults_state
from sparkdl_tpu.resilience.policy import (
    CircuitBreaker,
    CircuitOpen,
    RetryBudgetExhausted,
    RetryPolicy,
)

__all__ = [
    "CircuitBreaker",
    "CircuitOpen",
    "FaultSpecError",
    "InjectedFault",
    "InjectedPermanentFault",
    "PermanentError",
    "RetryBudgetExhausted",
    "RetryPolicy",
    "SITES",
    "TransientError",
    "classify",
    "default_retryable_exceptions",
    "disarm",
    "faults_state",
    "inject",
    "is_deterministic_jax_error",
    "is_transient",
    "maybe_fail",
]
