"""The model registry: versioned deployments, zero-downtime weight
hot-swap, and the fleet's one front door.

The control plane the paper's shared-cluster deployment story implies
(``DeepImagePredictor`` behind many tenants) and the TensorFlow system
paper argues for (PAPERS.md, arxiv 1605.08695): model LIFECYCLE —
what is deployed, at which version, with which weights, where — owned
separately from the data plane that executes batches. A
:class:`ModelRegistry` wraps a live :class:`ModelServer`: ``deploy``
registers a model at N replicas (optionally placement-pinned and
warm-started from the persisted AOT cache), ``swap_weights`` replaces
a deployment's params with ZERO downtime, and the router
(fleet/router.py) picks replicas per request.

The hot-swap contract, stated as invariants:

* **same compiled shape** — new params must match the old tree
  exactly (structure, leaf shapes, dtypes), checked FIRST; a mismatch
  is a typed :class:`SwapShapeError` refusal before any byte moves.
* **staged, then flipped** — new params are placed on device via
  ``ModelFunction.stage_params`` (the slow transfers, off the
  dispatch path), then made live by ``commit_params`` under each
  session's swap gate: the flip lands BETWEEN dispatches, requests
  in flight finish on the old weights, the next dispatch runs the
  new — nothing is dropped, nothing waits beyond one micro-batch.
* **retrace = failure** — after the flip, a probe batch runs through
  the steady program under PR 13's ``mark_model_steady`` /
  ``unexpected_retraces`` invariant. A swap that compiles ANYTHING
  is rolled back to the old params and raised as
  :class:`SwapRetraceError` — counted (``fleet.swap_rollbacks``),
  typed, loud. The mid-swap fault drill (``fleet.swap`` site) proves
  the rollback path: an injected failure between stage and commit
  leaves the old weights serving with zero dropped requests.

Every registry is weakly registered for the observability plane:
``/statusz``'s ``fleet`` field and flight bundles both render
:func:`fleet_state` — one shape, so a curl and a
postmortem never disagree (docs/SERVING.md).
"""

from __future__ import annotations

import hashlib
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from sparkdl_tpu.obs import default_registry
from sparkdl_tpu.obs.compile_log import compile_log
from sparkdl_tpu.resilience.faults import maybe_fail

from sparkdl_tpu.fleet.placement import PlacementPlan
from sparkdl_tpu.fleet.router import FleetRouter
from sparkdl_tpu.fleet.warmstart import WarmStartCache


class FleetError(Exception):
    """Base for fleet control-plane failures."""


class SwapError(FleetError):
    """A weight hot-swap failed. Always typed, always counted
    (``fleet.swap_failures``); when anything had already flipped, it
    was rolled back (``fleet.swap_rollbacks``) — the old weights are
    serving."""


class SwapShapeError(SwapError):
    """New params do not match the deployed tree (structure, leaf
    shapes, or dtypes) — refused BEFORE any transfer: a mismatched
    tree would retrace the steady program at dispatch time."""


class SwapRetraceError(SwapError):
    """The post-flip probe compiled something: the swap violated the
    same-compiled-shape contract in a way the static check could not
    see. The flip was rolled back; the old weights are serving."""


def params_fingerprint(params) -> str:
    """Content identity of a params pytree: structure + leaf bytes —
    the registry's version provenance (which weights are live?), NOT
    the warm-start key (which deliberately ignores values)."""
    import jax
    import numpy as np
    h = hashlib.blake2b(digest_size=16)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    h.update(repr(treedef).encode("utf-8"))
    for leaf in leaves:
        arr = np.asarray(leaf)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclass(frozen=True)
class ModelVersion:
    """One deployment version: monotonic number + weights
    fingerprint. Frozen — history is append-only."""
    version: int
    fingerprint: str
    note: str = ""


class RegistryEntry:
    """One deployed model: its reference ModelFunction, version
    history, replica session names, and the placement it was admitted
    under."""

    def __init__(self, name: str, model_fn, batch_size: int,
                 placement: Optional[PlacementPlan] = None):
        self.name = name
        self.model_fn = model_fn
        self.batch_size = int(batch_size)
        self.placement = placement
        self.versions: List[ModelVersion] = []
        self.replicas: List[str] = []
        self.warm_hits = 0

    @property
    def version(self) -> int:
        return self.versions[-1].version if self.versions else 0

    @property
    def fingerprint(self) -> str:
        return self.versions[-1].fingerprint if self.versions else ""

    def state(self) -> Dict[str, Any]:
        sig = {n: [list(int(d) if d is not None else -1
                        for d in shape), str(dtype)]
               for n, (shape, dtype)
               in self.model_fn.input_signature.items()}
        return {
            "name": self.name, "version": self.version,
            "fingerprint": self.fingerprint,
            "batch_size": self.batch_size,
            "replicas": list(self.replicas),
            "warm_hits": self.warm_hits,
            "signature": sig,
            "placement": (self.placement.as_dict()
                          if self.placement is not None else None),
            "history": [{"version": v.version,
                         "fingerprint": v.fingerprint,
                         "note": v.note}
                        for v in self.versions[-8:]],
        }


#: every live registry, weakly held — the flight/statusz renderer
#: (obs/flight.py fleet_state) reads these
_REGISTRIES: "weakref.WeakSet" = weakref.WeakSet()


def live_registries() -> List["ModelRegistry"]:
    return list(_REGISTRIES)


class ModelRegistry:
    """Versioned model deployments over one ModelServer (module
    docstring)."""

    # sparkdl-lint H3 contract: deploys/swaps mutate the entry table
    # while statusz renders it — entry-table writes hold self._lock
    _lock_guards = ("_entries",)

    def __init__(self, server, *,
                 warmstart: Optional[WarmStartCache] = None,
                 router: Optional[FleetRouter] = None):
        self._server = server
        self.router = router or FleetRouter(server)
        self.warmstart = warmstart or WarmStartCache()
        self._entries: Dict[str, RegistryEntry] = {}
        self._lock = threading.Lock()
        self.swaps = 0
        self.swap_failures = 0
        self.swap_rollbacks = 0
        self.last_swap_ms: Optional[float] = None
        _REGISTRIES.add(self)

    # -- deploy --------------------------------------------------------------

    def _replica_model(self, entry_name: str, model_fn, index: int,
                       device=None):
        """A per-replica ModelFunction: same apply_fn and params
        OBJECT as the reference (one flip covers all), its own
        jit/placement caches — and a device-pinned placement when the
        packing assigned one."""
        from sparkdl_tpu.graph.function import ModelFunction
        rmf = ModelFunction(
            model_fn.apply_fn, model_fn.params,
            model_fn.input_signature, model_fn._output_names,
            backend=model_fn.backend,
            name=f"{entry_name}@r{index}")
        rmf._output_signature = model_fn._output_signature
        rmf._fixed_batch = model_fn._fixed_batch
        # one program label for the deployment: its replicas then share
        # jax's compile of it, as they share the persisted executable
        rmf._program_name = model_fn._program_name or model_fn.name
        if device is not None:
            import jax
            dev = jax.devices()[device] if isinstance(device, int) \
                else device
            # seed the pinned placement NOW: the put is recorded for
            # stage_params, and the replica's params land on its
            # packed device before the first dispatch
            rmf._cached_device_params(
                "default", lambda p, d=dev: jax.device_put(p, d))
        return rmf

    def deploy(self, name: str, model_fn, *, batch_size: int = 64,
               replicas: int = 1,
               placement: Optional[PlacementPlan] = None,
               warmup: bool = True, note: str = "",
               **register_kw) -> RegistryEntry:
        """Register ``model_fn`` as ``name`` at ``replicas`` sessions
        (``name@r0`` … — each a full ModelSession with per-replica
        ``serve.*`` metrics), wire them into the router, warm each
        replica (persisted-AOT first: a cache hit installs the
        executable and the warmup batch compiles NOTHING), and record
        version 1. ``placement`` pins each replica to its packed
        device (fleet/placement.py); extra ``register_kw`` pass
        through to ``ModelServer.register``."""
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        with self._lock:
            if name in self._entries:
                raise ValueError(
                    f"model {name!r} already deployed (version "
                    f"{self._entries[name].version}); use "
                    "swap_weights for a weight update")
        entry = RegistryEntry(name, model_fn, batch_size,
                              placement=placement)
        devices = (placement.assignments.get(name)
                   if placement is not None else None)
        for i in range(replicas):
            device = (devices[i % len(devices)]
                      if devices else None)
            rmf = self._replica_model(name, model_fn, i,
                                      device=device)
            if self.warmstart.enabled:
                if self.warmstart.load(rmf, batch_size):
                    entry.warm_hits += 1
            rname = rmf.name
            session = self._server.register(
                rname, rmf, batch_size=batch_size, **register_kw)
            if warmup:
                session.warmup()
            entry.replicas.append(rname)
            self.router.add_replica(name, rname)
        if self.warmstart.enabled and entry.warm_hits < replicas:
            # first deployer persists the executable for the fleet:
            # the Nth scale-out replica, the next process, tomorrow's
            # redeploy all start warm from here
            self.warmstart.save(model_fn, batch_size)
        entry.versions.append(ModelVersion(
            1, params_fingerprint(model_fn.params), note))
        with self._lock:
            self._entries[name] = entry
            n_models = len(self._entries)
        default_registry().gauge("fleet.models").set(n_models)
        return entry

    def entry(self, name: str) -> RegistryEntry:
        with self._lock:
            try:
                return self._entries[name]
            except KeyError:
                raise ValueError(
                    f"unknown model {name!r}; deployed: "
                    f"{sorted(self._entries)}") from None

    def submit(self, inputs, deadline: Optional[float] = None,
               model: Optional[str] = None, priority: int = 0):
        """The fleet front door: route to the best replica and
        submit (fleet/router.py)."""
        return self.router.submit(inputs, deadline=deadline,
                                  model=model, priority=priority)

    # -- hot swap ------------------------------------------------------------

    @staticmethod
    def _check_same_tree(old_params, new_params) -> None:
        import jax
        old_leaves, old_def = jax.tree_util.tree_flatten(old_params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if old_def != new_def:
            raise SwapShapeError(
                f"params tree structure changed: {old_def} -> "
                f"{new_def} — a hot-swap must keep the compiled "
                "shape; deploy under a new name instead")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            os_, ns = (tuple(getattr(o, "shape", ())),
                       tuple(getattr(n, "shape", ())))
            od, nd = (str(getattr(o, "dtype", "?")),
                      str(getattr(n, "dtype", "?")))
            if os_ != ns or od != nd:
                raise SwapShapeError(
                    f"params leaf {i} changed {os_}/{od} -> "
                    f"{ns}/{nd} — a hot-swap must keep the compiled "
                    "shape; deploy under a new name instead")

    def _probe_zero_retrace(self, entry: RegistryEntry) -> None:
        """One zeros batch through each replica's steady program,
        watching the compile ledger: ANY compile (or unexpected
        retrace) after the flip means the swap changed the compiled
        shape in a way the static check missed — typed failure, the
        caller rolls back."""
        import numpy as np
        clog = compile_log()
        sig = entry.model_fn.input_signature
        if any(d is None for shape, _ in sig.values() for d in shape):
            return      # no concrete probe batch exists
        before_unexpected = clog.unexpected_retraces
        for rname in entry.replicas:
            sess = self._server.session(rname)
            rmf = sess.runner.model_fn
            if rmf.backend != "jax":
                continue
            before = clog.compiles_of(f"{rmf.name}.jitted")
            zeros = {
                k: np.zeros((entry.batch_size,) + tuple(shape), dtype)
                for k, (shape, dtype) in sig.items()}
            rmf.jitted()(rmf.device_params(),
                         {k: v for k, v in zeros.items()})
            after = clog.compiles_of(f"{rmf.name}.jitted")
            if after > before:
                raise SwapRetraceError(
                    f"replica {rname!r} COMPILED on the post-swap "
                    "probe (the staged params changed the compiled "
                    "shape) — rolling back to the old weights")
        if clog.unexpected_retraces > before_unexpected:
            raise SwapRetraceError(
                "the post-swap probe counted an unexpected retrace "
                "of a steady program — rolling back to the old "
                "weights")

    def swap_weights(self, name: str, new_params,
                     note: str = "") -> ModelVersion:
        """Replace ``name``'s weights with zero downtime (module
        docstring): shape-check, stage to every replica placement,
        flip each replica under its swap gate, probe for retraces.
        Any failure past staging rolls EVERY flipped replica back to
        the old params — concurrent submitters never see a dropped
        request or a half-swapped fleet. Returns the new version."""
        entry = self.entry(name)
        t0 = time.perf_counter()
        old_params = entry.model_fn.params
        try:
            self._check_same_tree(old_params, new_params)
        except SwapShapeError:
            self.swap_failures += 1
            default_registry().counter("fleet.swap_failures").add()
            raise
        # stage every replica OUTSIDE the gates: the transfers are the
        # slow half, and the dispatchers keep serving old weights
        # through all of it
        staged = []
        for rname in entry.replicas:
            sess = self._server.session(rname)
            rmf = sess.runner.model_fn
            staged.append((sess, rmf, rmf.params,
                           dict(rmf._params_cache),
                           rmf.stage_params(new_params)
                           if rmf.backend == "jax" else {}))
        flipped = []
        try:
            # the mid-swap drill seam (resilience/faults.py): staged
            # but not yet live — an injected failure here proves the
            # rollback path with the old weights still serving
            maybe_fail("fleet.swap")
            for sess, rmf, _old_p, _old_cache, stg in staged:
                with sess._swap_gate:
                    if rmf.backend == "jax":
                        rmf.commit_params(new_params, stg)
                    else:
                        rmf.params = new_params
                flipped.append((sess, rmf))
            entry.model_fn.params = new_params
            self._probe_zero_retrace(entry)
        except BaseException as e:
            # roll back every flipped replica under its gate — the
            # fleet is never left half-swapped
            for (sess, rmf, old_p, old_cache, _stg), _f in zip(
                    staged, flipped):
                with sess._swap_gate:
                    rmf.params = old_p
                    rmf._params_cache = old_cache
            entry.model_fn.params = old_params
            self.swap_failures += 1
            default_registry().counter("fleet.swap_failures").add()
            if flipped:
                self.swap_rollbacks += 1
                default_registry().counter(
                    "fleet.swap_rollbacks").add()
            if isinstance(e, SwapError):
                raise
            raise SwapError(
                f"hot-swap of {name!r} failed mid-swap "
                f"({type(e).__name__}: {e}); rolled back to version "
                f"{entry.version} — the old weights are serving"
            ) from e
        version = ModelVersion(entry.version + 1,
                               params_fingerprint(new_params), note)
        entry.versions.append(version)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        self.swaps += 1
        self.last_swap_ms = round(wall_ms, 3)
        reg = default_registry()
        reg.counter("fleet.swaps").add()
        reg.gauge("fleet.swap_latency_ms").set(wall_ms)
        return version

    # -- scale (the autotune knob's apply point) -----------------------------

    def scale(self, name: str, replicas: int,
              **register_kw) -> int:
        """Grow ``name`` to ``replicas`` sessions (grow-only: extra
        live replicas keep serving; the autotune knob never tears
        down a session mid-traffic). New replicas warm-start from the
        persisted cache — which is the whole point of scaling being
        cheap. Returns the live replica count."""
        entry = self.entry(name)
        while len(entry.replicas) < int(replicas):
            i = len(entry.replicas)
            rmf = self._replica_model(name, entry.model_fn, i)
            if self.warmstart.enabled:
                if self.warmstart.load(rmf, entry.batch_size):
                    entry.warm_hits += 1
            session = self._server.register(
                rmf.name, rmf, batch_size=entry.batch_size,
                **register_kw)
            session.warmup()
            entry.replicas.append(rmf.name)
            self.router.add_replica(name, rmf.name)
        return len(entry.replicas)

    # -- readout -------------------------------------------------------------

    def state(self) -> Dict[str, Any]:
        """ONE shape shared by ``/statusz`` and flight bundles (the
        flight-renderer discipline)."""
        with self._lock:
            entries = {name: e.state()
                       for name, e in sorted(self._entries.items())}
        return {
            "models": entries,
            "swaps": self.swaps,
            "swap_failures": self.swap_failures,
            "swap_rollbacks": self.swap_rollbacks,
            "last_swap_ms": self.last_swap_ms,
            "router": self.router.state(),
            "warmstart": self.warmstart.state(),
        }

    # -- pickle discipline (StageMetrics precedent) --------------------------

    def __getstate__(self):
        """Locks and the live server drop; entries (versions,
        fingerprints, replica names, batch sizes) and the warm-start
        config travel — an unpickled registry is the deployment
        RECORD, inspectable anywhere, re-attachable via attach()."""
        state = self.__dict__.copy()
        del state["_lock"]
        state["_server"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = threading.Lock()
        _REGISTRIES.add(self)

    def attach(self, server) -> None:
        """Re-bind a live server after unpickling."""
        self._server = server
        self.router.attach(server)
