"""Runtime sanitizer: make the JAX runtime itself enforce the
zero-copy ship-path claim.

``SPARKDL_TPU_SANITIZE=1`` arms :func:`ship_guard`, which the batch
runners (BatchRunner._run_device, ShardedBatchRunner.run) enter around
their dispatch/drain loop. Inside it,
``jax.transfer_guard_device_to_host("disallow")`` turns any IMPLICIT
device→host transfer — an ``np.asarray`` on a device value, a
``float()``/``bool()`` materialization, a library helper quietly
syncing — into an immediate error at the offending line. The explicit
drain (``SlabSink.write`` → ``obs.timed_device_get``) and the explicit
input-side ``jax.device_put`` (prefetch/sharded placement) stay legal:
the guard bans the transfers nobody *meant* to write, which is exactly
the class of regression sparkdl-lint's H1 rule hunts statically — this
module is the dynamic half of that pair.

``SPARKDL_TPU_SANITIZE_NANS=1`` additionally flips ``jax_debug_nans``
(process-global, set once on first armed entry): aligned runs then
fault at the op that produced a NaN instead of shipping it.

``SPARKDL_TPU_SANITIZE=1`` also arms :func:`assert_lock_owned` — the
dynamic half of the H17 guarded-by pair the way ship_guard is H1's:
caller-holds-the-lock helpers (serve queue shedding, the pipeline pool
registry) assert their contract on entry, so the suppressions the
static race rules carry are re-validated on every sanitized run
instead of trusted forever.

A backend on which the guard fails to arm degrades ONCE, with a warning
(``sanitize.degrade_events``): sanitizing must never change whether a
run completes, only whether a contract violation surfaces.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator

from sparkdl_tpu.obs import default_registry

_TRUE = ("1", "true", "yes", "on")

_warned_no_guard = False
_nans_configured = False
_armed_runs = 0


def sanitize_enabled() -> bool:
    """Read the env each call (cheap) so tests and long-lived workers
    can arm/disarm without re-importing."""
    return os.environ.get("SPARKDL_TPU_SANITIZE", "").lower() in _TRUE


def armed_run_count() -> int:
    """How many times :func:`ship_guard` actually ARMED the transfer
    guard in this process. Reporters must use this, not :func:`sanitize_enabled`: the env var only asks for
    enforcement — a backend without the guard API degrades with a
    warning, and claiming "enforced" then would hide exactly the
    regression class the sanitizer exists to catch."""
    return _armed_runs


def debug_nans_requested() -> bool:
    return os.environ.get("SPARKDL_TPU_SANITIZE_NANS",
                          "").lower() in _TRUE


def _configure_debug_nans_once() -> None:
    global _nans_configured
    if _nans_configured or not debug_nans_requested():
        return
    _nans_configured = True
    import jax
    jax.config.update("jax_debug_nans", True)
    logging.getLogger(__name__).info(
        "sanitizer: jax_debug_nans enabled (SPARKDL_TPU_SANITIZE_NANS)")


def assert_lock_owned(lock, what: str) -> None:
    """Debug cross-check for the static guarded-by model (sparkdl-lint
    H17): private helpers whose contract is "caller holds the lock" —
    the serve queue's shed helpers, the pipeline pool registry — call
    this on entry so the contract the analyzer takes on faith (and the
    suppression documents) is VALIDATED on every sanitized run. No-op unless
    ``SPARKDL_TPU_SANITIZE=1``: steady-state serving pays nothing.

    An RLock/Condition knows its owner (``_is_owned``); a plain Lock
    only knows it is held at all (``locked``) — good enough to catch
    the real regression shape, a refactor that starts calling the
    helper outside any hold."""
    if not sanitize_enabled():
        return
    if lock is None:
        raise AssertionError(
            f"sanitizer: {what} requires its guard lock held, but no "
            "guard is attached (the owner never handed one over)")
    probe = getattr(lock, "_is_owned", None)
    owned = probe() if callable(probe) else lock.locked()
    if not owned:
        default_registry().counter("sanitize.lock_violations").add()
        raise AssertionError(
            f"sanitizer: {what} called without its guard lock held — "
            "the caller-holds contract sparkdl-lint H17 suppresses on "
            "is broken here")


@contextlib.contextmanager
def ship_guard() -> Iterator[bool]:
    """Context for the runners' dispatch/drain loop; yields whether the
    transfer guard is actually armed (False: sanitize off, or backend
    degraded). Implicit device→host transfers inside the block raise;
    explicit device_put/device_get pass."""
    if not sanitize_enabled():
        yield False
        return
    global _warned_no_guard
    import jax
    _configure_debug_nans_once()
    guard = jax.transfer_guard_device_to_host("disallow")
    try:
        guard.__enter__()
    except (NotImplementedError, RuntimeError) as e:
        # probe-and-degrade: an unsupported backend must not turn the
        # sanitizer into an availability bug
        if not _warned_no_guard:
            _warned_no_guard = True
            logging.getLogger(__name__).warning(
                "SPARKDL_TPU_SANITIZE=1 but transfer_guard failed to "
                "arm (%s); ship path runs unguarded", e)
        default_registry().counter("sanitize.degrade_events").add()
        yield False
        return
    global _armed_runs
    _armed_runs += 1
    default_registry().counter("sanitize.armed_runs").add()
    try:
        yield True
    finally:
        guard.__exit__(None, None, None)
