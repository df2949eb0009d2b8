"""Sharded data-parallel inference over a device mesh.

Multi-chip counterpart of ``runtime/runner.py::BatchRunner`` — the
reference's core strategy scaled the TPU way (SURVEY §2.4 "data
parallelism (inference)"): the reference replicated the frozen graph to
every Spark executor and gave each a partition; here the jitted program
is compiled once against a ``Mesh``, params replicated to every chip,
and each global batch's leading dim is split over the ``data`` axis —
host→device transfer of batch *i+1* overlaps device compute of batch
*i* via JAX async dispatch, exactly like the single-chip runner.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import span
from sparkdl_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshSpec,
    collective_launch,
    data_sharding,
    make_mesh,
    mesh_has_collectives,
)
from sparkdl_tpu.runtime.runner import (
    BoundaryCarry,
    ChunkPhases,
    CopyCounters,
    PadStaging,
    RunnerMetrics,
    SlabSink,
    check_against_signature,
    check_row_counts,
    checkout_staging,
    dispatch_chunks,
    empty_jax_outputs,
    iter_padded_chunks,
    record_run_feeds,
    resolve_max_inflight,
    warmup_runner,
)
from sparkdl_tpu.runtime.sanitize import ship_guard


class ShardedBatchRunner:
    """Runs a jax-backend ModelFunction data-parallel over a mesh.

    ``batch_size`` is the PER-CHIP batch; the global device batch is
    ``batch_size * mesh.shape["data"]``.
    """

    # run() accepts the phases= accumulator (runtime/runner.py
    # ChunkPhases) — the serve layer probes this attribute
    supports_phases = True

    def __init__(self, model_fn: ModelFunction, mesh: Optional[Mesh] = None,
                 batch_size: int = 64,
                 metrics: Optional[RunnerMetrics] = None,
                 max_inflight: Optional[int] = None):
        if model_fn.backend != "jax":
            raise ValueError(
                f"sharded execution requires a jax backend, got "
                f"'{model_fn.backend}' for {model_fn.name}")
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        self.model_fn = model_fn
        # default: THIS process's devices — a global mesh over
        # non-addressable devices can't consume host-local numpy batches
        self.mesh = mesh if mesh is not None else make_mesh(
            devices=jax.local_devices())
        self.batch_size = batch_size
        self.metrics = metrics or RunnerMetrics()
        # the in-flight window's depth, validated like BatchRunner's
        self.max_inflight = resolve_max_inflight(max_inflight)
        self._global_batch = batch_size * self.mesh.shape[DATA_AXIS]
        # persistent pad staging (BatchRunner's checkout discipline):
        # concurrent run() calls fall back to a throwaway stager
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()
        # the in-flight window between two run() calls
        self._carry = BoundaryCarry()

    # Locks, warm staging buffers, and the mesh's device handles are
    # process-local; a runner captured in a stage closure ships to
    # Spark executors (spark_binding) — drop them on the wire and
    # rebuild on arrival, the same discipline as BatchRunner /
    # RunnerMetrics. The mesh's AXIS STRUCTURE (its model-axis width)
    # does ship: the receiving process re-derives devices from ITS
    # local topology but keeps the parallelism layout, so a
    # model-parallel runner stays model-parallel (a host whose device
    # count can't satisfy the layout fails loudly in MeshSpec.resolve
    # rather than silently collapsing to pure DP). preferred_chunk may
    # legitimately differ across hosts — each sizes global batches by
    # its own data-axis width.
    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_staging", None)
        state.pop("_staging_lock", None)
        state.pop("mesh", None)
        state.pop("_global_batch", None)
        state["_mesh_model_axis"] = self.mesh.shape[MODEL_AXIS]
        return state

    def __setstate__(self, state):
        model_axis = state.pop("_mesh_model_axis", 1)
        self.__dict__.update(state)
        self.mesh = make_mesh(MeshSpec(data=-1, model=model_axis),
                              devices=jax.local_devices())
        self._global_batch = self.batch_size * self.mesh.shape[DATA_AXIS]
        self._staging = PadStaging()
        self._staging_lock = threading.Lock()

    def drop_carry(self) -> None:
        """Forget device batches a ``run(..., upcoming=...)`` of this
        thread left in flight (``BatchRunner.drop_carry``)."""
        self._carry.drop()

    @property
    def preferred_chunk(self) -> int:
        """Row count at which run() pads nothing: the GLOBAL mesh batch
        (per-chip batch × data-axis size) — published as the device
        stage's plan batch_hint."""
        return self._global_batch

    def warmup(self) -> bool:
        """Pre-trace/compile the sharded program at the global mesh
        batch shape (one zeros run of ``preferred_chunk`` rows) so the
        first real ``run()`` pays no compile — the warmup goes through
        :meth:`run`, so a model-parallel program's first launch already
        holds the collective launch lock. See
        :func:`~sparkdl_tpu.runtime.runner.warmup_runner`."""
        return warmup_runner(self)

    def run(self, inputs: Dict[str, np.ndarray],
            phases: Optional[ChunkPhases] = None,
            upcoming=None) -> Dict[str, np.ndarray]:
        """inputs: {name: [N, *row_shape]} → {name: [N, *out_shape]};
        N is cut into global batches, the tail padded then truncated.
        ``phases`` (optional) accumulates placement/enqueue/drain
        timestamps for per-request attribution (runtime/runner.py).
        ``upcoming`` (optional) announces the next call's inputs, whose
        first global batches are then dispatched under this call's
        last steps and left in flight, owned by the runner: the
        contract is ``BatchRunner.run``'s
        (runtime/runner.py::BoundaryCarry), the code
        ``dispatch_chunks``'. Without it nothing is left in flight."""
        n = check_row_counts(inputs)
        if n == 0:  # before the signature check: empty flat inputs
            return empty_jax_outputs(self.model_fn)
        check_against_signature(inputs, self.model_fn)

        # compile + replicate lazily, cached on the ModelFunction so
        # multiple runners over the same model share one program and one
        # device copy of the weights
        fn = self.model_fn.sharded_jitted(self.mesh)
        params = self.model_fn.replicated_params(self.mesh)

        # Single-process jit accepts numpy args and shards them itself;
        # a multi-process runtime refuses numpy for non-trivially
        # sharded args even on an all-local mesh — place each chunk
        # explicitly there (all this mesh's devices are addressable, so
        # the device_put is purely local).
        place = None
        if jax.process_count() > 1:
            dat = data_sharding(self.mesh)
            place = lambda c: {k: jax.device_put(v, dat)  # noqa: E731
                               for k, v in c.items()}

        # the span opens where ``t0`` is read and closes where
        # ``elapsed`` is: it times what RunnerMetrics.seconds times
        with span("runner.run_sharded", lane="ship", rows=n,
                  mesh=f"{self.mesh.shape[DATA_AXIS]}x"
                       f"{self.mesh.shape[MODEL_AXIS]}"):
            t0 = time.perf_counter()
            sink = SlabSink(n)
            counters = CopyCounters()
            staging, locked = checkout_staging(self._staging,
                                               self._staging_lock)
            try:
                # the shared dispatch loop (runtime/runner.py);
                # SPARKDL_TPU_SANITIZE=1 arms
                # transfer_guard around it (runtime/sanitize.py —
                # explicit place/drain stay legal). A model-parallel
                # program carries collectives, so its launches must
                # not interleave with another thread's
                # (parallel/mesh.py::collective_launch); the pure-DP
                # forward has no cross-device edges and stays
                # lock-free (the policy lives in mesh_has_collectives
                # — the serve layer reads the same predicate).
                launch = collective_launch(
                    self.mesh if mesh_has_collectives(self.mesh)
                    else None)
                with self._carry.window(
                        inputs, upcoming, self._global_batch,
                        self.model_fn, counters,
                        uncontended=locked) as carry, \
                        launch, ship_guard():
                    chunks = iter_padded_chunks(
                        inputs, n, self._global_batch, staging,
                        counters, start=carry.rows_in_flight)
                    batches = dispatch_chunks(
                        fn, params, chunks, self.max_inflight, sink,
                        place=place, phases=phases, carry=carry)
            finally:
                if locked:
                    self._staging_lock.release()
            if phases is not None:
                # drain half of the phase accounting — one pair of
                # clock reads shared with transfer_wait_seconds
                phases.drain_s += sink.transfer_wait
            elapsed = time.perf_counter() - t0
        self.metrics.add(n, batches, elapsed,
                         bytes_staged=counters.bytes_staged,
                         bytes_copied=counters.bytes_copied,
                         transfer_wait_seconds=sink.transfer_wait,
                         began=carry.began)
        from sparkdl_tpu.obs.compile_log import compile_log
        record_run_feeds(self.model_fn, inputs, elapsed,
                         sink.transfer_wait, batches=batches,
                         flops_per_batch=(
                             getattr(fn, "last_flops", None)
                             if compile_log().armed else None))
        # autotune apply point (runtime/runner.py precedent): knobs
        # move between runs only; disarmed this is one armed-check
        from sparkdl_tpu.autotune.core import poll as autotune_poll
        autotune_poll()
        from sparkdl_tpu.obs.ledger import ledger_poll
        ledger_poll()
        return sink.result()
