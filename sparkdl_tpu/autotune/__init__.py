"""Closed-loop infeed autotuner (docs/PERFORMANCE.md).

The layer that makes the measured pipeline self-driving: a
measure→decide→apply controller
(:mod:`sparkdl_tpu.autotune.core`) reads the per-window rates
the pipeline already records (``RunnerMetrics``, ``ServeMetrics``,
the obs registry) and moves the shape-safe throughput knobs at
runtime through attachable targets
(:mod:`sparkdl_tpu.autotune.targets`):

* ``ServeTarget`` — the serve dispatcher's coalesce window
  (``ModelSession.max_wait_s``): shrunk when batch fill saturates,
  grown when fill is poor and p99 headroom exists;
* ``RechunkTarget`` — the device batch / engine re-chunk hint, moved
  only along a pre-warmed shape ladder (zero cold retraces);
* ``PipelineTarget`` — the parallel host pipeline's worker count and
  read-ahead window (``data/pipeline.py``): deepened (trial-gated)
  while the live roofline says the decode lane binds, shed on memory
  pressure;
* ``FleetTarget`` — a fleet-registry model's replica count
  (``sparkdl_tpu/fleet``): grown (grow-only, warm-started from the
  persisted AOT cache) only while the roofline says the serve lane
  binds AND replica queues stay deep.

Armed by ``SPARKDL_TPU_AUTOTUNE=1`` or ``controller().arm()``;
disarmed, the hot-path :func:`poll` hook is a single armed-check (the
tracer's shared-no-op regime). Decisions use hysteresis + bounded
steps and are fully observable: the ``autotune`` span lane,
``autotune.decisions/oscillations/clamps`` registry counters,
``autotune.knob.*`` gauges, and controller state in every flight
bundle.
"""

from sparkdl_tpu.autotune.core import (
    AutotuneController,
    Knob,
    Proposal,
    controller,
    poll,
)
from sparkdl_tpu.autotune.targets import (
    FleetTarget,
    PipelineTarget,
    RechunkTarget,
    ServeTarget,
)

__all__ = [
    "AutotuneController",
    "FleetTarget",
    "Knob",
    "PipelineTarget",
    "Proposal",
    "RechunkTarget",
    "ServeTarget",
    "controller",
    "poll",
]
