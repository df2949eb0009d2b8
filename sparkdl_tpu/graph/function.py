"""ModelFunction: the deployable unit of compute.

TPU-native re-design of the reference's
``python/sparkdl/graph/builder.py::GraphFunction`` (frozen GraphDef +
input/output names) and ``IsolatedSession`` (hermetic graph build +
``asGraphFunction`` freeze). A ModelFunction is:

* ``apply_fn(params, inputs: dict[str, Array]) -> dict[str, Array]`` — a
  pure function; ``params`` is a pytree (the reference froze variables
  into graph constants; here they stay an explicit pytree, and "freezing"
  is ``export()`` which bakes them into serialized StableHLO).
* named input/output signatures (per-row shapes, batch dim implicit) —
  the counterpart of the reference's tensor-name mappings.
* ``fromList`` composition replacing GraphFunction.fromList's GraphDef
  import/re-export surgery: plain function composition, fused by XLA
  into one program at jit time.

No session isolation is needed: JAX is functional, so the reference's
``IsolatedSession``/``KSessionWrap`` global-state hygiene (builder.py,
keras_utils.py) has no analogue — that entire failure class is gone.

A ModelFunction may instead wrap an opaque **host** callable (backend
"host") for ingested TF-era graphs that execute via the TF CPU runtime —
the same place the reference executed them (executor CPUs via JNI
libtensorflow); see ``graph/ingest.py`` for the boundary.
"""

from __future__ import annotations

import functools
import re
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu.obs.compile_log import compile_log, listen
from sparkdl_tpu.obs.registry import default_registry

# every program of the package is built through this module: from its
# import on, THE compile log hears what jax traces, lowers and compiles
# (obs/compile_log.py, "The phases"), the builder's own ``init`` too
listen()

# name -> (per-row shape tuple, dtype)
Signature = Dict[str, Tuple[Tuple[int, ...], Any]]

# (id(apply_fn), label) -> the labelled wrapper ``ModelFunction._program``
# jits. ModelFunctions over one ``apply_fn`` under one label (a fleet's
# replicas) get ONE wrapper, so jax's trace and executable caches, which
# key on the function's identity, still serve them all with one
# compile. Weak values: the jitted callables hold the wrapper, the
# wrapper holds ``apply_fn``, and the entry goes with the last of them.
_PROGRAMS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def _as_dict(x, names: Sequence[str]) -> Dict[str, Any]:
    if isinstance(x, dict):
        return x
    if len(names) != 1:
        raise ValueError(
            f"got a single array for multi-input function {list(names)}")
    return {names[0]: x}


class ModelFunction:
    """A named-IO pure function + params, composable and exportable."""

    def __init__(self,
                 apply_fn: Callable[[Any, Dict[str, jax.Array]],
                                    Dict[str, jax.Array]],
                 params: Any = None,
                 input_signature: Optional[Signature] = None,
                 output_names: Optional[Sequence[str]] = None,
                 backend: str = "jax",
                 name: str = "model_fn"):
        self.apply_fn = apply_fn
        self.params = params
        self.input_signature: Signature = dict(input_signature or {})
        self._output_names = list(output_names) if output_names else None
        self.backend = backend
        self.name = name
        # the compiled program's label when it is not this function's
        # own name: a fleet replica carries its deployment's
        self._program_name: Optional[str] = None
        self._jit_cache: Dict[Any, Callable] = {}
        # device copies of params keyed by placement; each entry keeps
        # the host object it was built from so reassigning .params
        # invalidates it
        self._params_cache: Dict[Any, Tuple[Any, Any]] = {}
        # the put callable behind each placement key, recorded so the
        # fleet hot-swap (stage_params) can re-place NEW params onto
        # exactly the placements this process serves — including a
        # device-pinned put the registry seeded for a packed replica
        self._puts: Dict[Any, Callable] = {}
        # known output signature (set by deserialize, which reads it
        # from the exported avals); when present, output_signature()
        # returns it instead of eval_shape-probing — a fixed-batch
        # exported program rejects any other batch size
        self._output_signature: Optional[Signature] = None
        # the ONLY batch size a fixed-batch exported program accepts
        # (set by deserialize; propagated by wrappers). eval_shape
        # probes must use it — batch-1 probes crash such programs.
        self._fixed_batch: Optional[int] = None

    # -- construction -------------------------------------------------------

    @staticmethod
    def fromSingle(fn: Callable, params: Any = None,
                   input_shape: Tuple[int, ...] = (),
                   input_dtype=jnp.float32,
                   input_name: str = "input",
                   output_name: str = "output",
                   name: str = "model_fn") -> "ModelFunction":
        """Wrap a single-tensor function ``fn(params, x) -> y`` (or
        ``fn(x) -> y`` when params is None)."""

        def apply_fn(params_, inputs):
            x = inputs[input_name]
            y = fn(params_, x) if params_ is not None else fn(x)
            if isinstance(y, dict):
                return y
            return {output_name: y}

        return ModelFunction(
            apply_fn, params,
            input_signature={input_name: (tuple(input_shape), input_dtype)},
            output_names=[output_name], name=name)

    @staticmethod
    def fromList(functions: Sequence["ModelFunction"],
                 name: str = "composed") -> "ModelFunction":
        """Chain single-output→single-input functions into one
        (reference ``GraphFunction.fromList``). The composite is one
        jittable function; XLA fuses the stages."""
        functions = list(functions)
        if not functions:
            raise ValueError("fromList needs at least one function")
        for f in functions:
            if f.backend != "jax":
                raise ValueError(
                    f"fromList requires jax-backend functions, got "
                    f"'{f.backend}' for {f.name}")
        head = functions[0]

        def apply_fn(params_list, inputs):
            cur = inputs
            out: Dict[str, jax.Array] = {}
            for i, f in enumerate(functions):
                out = f.apply_fn(params_list[i], cur)
                if i + 1 < len(functions):
                    out_names = list(out)
                    if len(out_names) != 1:
                        raise ValueError(
                            f"stage {f.name} has {len(out_names)} outputs; "
                            "fromList chains single-output stages")
                    nxt_in = functions[i + 1].input_names
                    if len(nxt_in) != 1:
                        raise ValueError(
                            f"stage {functions[i+1].name} has "
                            f"{len(nxt_in)} inputs; fromList chains "
                            "single-input stages")
                    cur = {nxt_in[0]: out[out_names[0]]}
            return out

        return ModelFunction(
            apply_fn,
            params=[f.params for f in functions],
            input_signature=dict(head.input_signature),
            output_names=functions[-1]._output_names,
            name=name)

    # -- introspection ------------------------------------------------------

    @property
    def input_names(self) -> List[str]:
        return list(self.input_signature)

    @property
    def output_names(self) -> List[str]:
        if self._output_names is None:
            self._output_names = list(self.output_signature())
        return list(self._output_names)

    def output_signature(self, batch_size: int = 1) -> Signature:
        """Infer named output shapes via ``jax.eval_shape`` (per-row
        shapes, batch stripped); deserialized models return the
        signature recorded in the export instead of probing, and
        wrappers around a fixed-batch deserialized program probe with
        ITS batch size (any other size is rejected by the export)."""
        if self._output_signature is not None:
            return dict(self._output_signature)
        if self.backend != "jax":
            raise ValueError("output_signature requires a jax backend")
        if self._fixed_batch is not None:
            batch_size = self._fixed_batch
        inputs = {
            n: jax.ShapeDtypeStruct((batch_size,) + tuple(shape), dtype)
            for n, (shape, dtype) in self.input_signature.items()
        }
        out = jax.eval_shape(self.apply_fn, self.params, inputs)
        return {n: (tuple(s.shape[1:]), s.dtype) for n, s in out.items()}

    def rename_io(self, input_map: Optional[Dict[str, str]] = None,
                  output_map: Optional[Dict[str, str]] = None
                  ) -> "ModelFunction":
        """New ModelFunction with renamed inputs/outputs (the counterpart
        of the reference's signature-name↔tensor-name translation,
        ``graph/input.py::translateInputMapping``)."""
        input_map = input_map or {}
        output_map = output_map or {}
        inv_in = {new: old for old, new in input_map.items()}
        base = self

        def apply_fn(params_, inputs):
            renamed = {inv_in.get(n, n): v for n, v in inputs.items()}
            out = base.apply_fn(params_, renamed)
            return {output_map.get(n, n): v for n, v in out.items()}

        sig = {input_map.get(n, n): v
               for n, v in self.input_signature.items()}
        out_names = ([output_map.get(n, n) for n in self._output_names]
                     if self._output_names else None)
        out = ModelFunction(apply_fn, self.params, sig, out_names,
                            backend=self.backend,
                            name=f"{self.name}.renamed")
        out._fixed_batch = self._fixed_batch
        if self._output_signature is not None:
            out._output_signature = {
                output_map.get(n, n): v
                for n, v in self._output_signature.items()}
        return out

    # -- execution ----------------------------------------------------------

    def _program(self) -> Callable:
        """``apply_fn`` under a label derived from :attr:`name`
        (sanitised to ``[A-Za-z0-9_]``): ``jax.jit`` names the compiled
        program after the function's ``__name__`` (``jit_<label>`` on
        the profiler's ``XLA Modules`` line and at the head of every
        instruction's ``op_name``), so the label follows the model and
        not whatever ``apply_fn`` happens to be called. The wrapper adds
        no operation, and is shared (``_PROGRAMS``)."""
        apply_fn = self.apply_fn
        label = re.sub(r"[^A-Za-z0-9_]", "_",
                       self._program_name or self.name)
        program = _PROGRAMS.get((id(apply_fn), label))
        if program is None:
            def program(params, inputs):
                return apply_fn(params, inputs)

            program.__name__ = program.__qualname__ = label
            _PROGRAMS[(id(apply_fn), label)] = program
        return program

    def _cached_device_params(self, key, put: Callable):
        self._puts[key] = put
        entry = self._params_cache.get(key)
        if entry is None or entry[0] is not self.params:
            # params changed: purge EVERY stale placement, not just this
            # key — dead replicated copies would otherwise hold HBM on
            # all devices for the ModelFunction's lifetime
            self._params_cache = {
                k: v for k, v in self._params_cache.items()
                if v[0] is self.params}
            # a cache miss is a weight transfer the compile forensics
            # want on the books (obs/compile_log.py): each placement
            # holds param-sized HBM for the ModelFunction's lifetime,
            # and a steady process re-placing weights is the same
            # class of hot-path surprise as a retrace
            # and of set-up: timed always (once a ModelFunction and
            # placement; never on a call that finds its entry). The
            # seconds are the host's inside ``put``: nothing here
            # waits for the bytes to land.
            t0 = time.perf_counter()
            placed = put(self.params)
            wall = time.perf_counter() - t0
            leaves = jax.tree_util.tree_leaves(self.params)
            nbytes = sum(int(getattr(v, "nbytes", 0)) for v in leaves)
            reg = default_registry()
            reg.counter("ship.params_place_seconds").add(wall)
            reg.counter("ship.params_bytes").add(nbytes)
            log = compile_log()
            if log.armed:
                log.record_transfer(
                    name=f"{self.name}.device_params", kind="device_put",
                    wall_s=wall,
                    detail={"placement": (key if isinstance(key, str)
                                          else key[0]),
                            "leaves": len(leaves),
                            "bytes": nbytes})
            entry = (self.params, placed)
            self._params_cache[key] = entry
        return entry[1]

    def device_params(self):
        """``params`` resident on the default device, transferred once
        and cached — passing the host pytree to every jitted call would
        re-transfer each weight leaf per call. Cache is keyed on the
        params object's identity, so reassigning ``self.params``
        invalidates it."""
        if self.backend != "jax" or self.params is None:
            return self.params
        return self._cached_device_params("default", jax.device_put)

    def replicated_params(self, mesh):
        """``params`` replicated to every device of ``mesh``, cached per
        mesh (the sharded-inference analogue of :meth:`device_params`)."""
        if self.backend != "jax" or self.params is None:
            return self.params
        from sparkdl_tpu.parallel.mesh import replicated
        sharding = replicated(mesh)
        return self._cached_device_params(
            ("replicated", mesh), lambda p: jax.device_put(p, sharding))

    def sharded_jitted(self, mesh) -> Callable:
        """Jit compiled against ``mesh``: params replicated, every named
        input/output batch-sharded over the ``data`` axis — the same
        axis name ShardedBatchRunner sizes its global batches by
        (cached per mesh, like :meth:`jitted`)."""
        if self.backend != "jax":
            raise ValueError(f"cannot jit backend '{self.backend}'")
        key = ("sharded", mesh)
        if key not in self._jit_cache:
            from sparkdl_tpu.parallel.mesh import data_sharding, replicated
            rep = replicated(mesh)
            dat = data_sharding(mesh)
            fn = jax.jit(
                self._program(),
                in_shardings=(rep, {k: dat for k in self.input_names}),
                out_shardings=dat)
            # route compiles through the process-wide CompileLog
            # (obs/compile_log.py): retrace attribution + cost/memory
            # accounting; one armed-check + passthrough disarmed
            self._jit_cache[key] = compile_log().instrument(
                fn, name=f"{self.name}.sharded_jitted",
                kind="sharded_jit",
                config={"mesh": tuple(mesh.shape.items()),
                        "in_shardings": "replicated+data",
                        "out_shardings": "data"},
                arg_names=("params", "inputs"))
        return self._jit_cache[key]

    def jitted(self) -> Callable:
        """Jit-compiled ``(params, inputs) -> outputs`` (cached)."""
        if self.backend != "jax":
            raise ValueError(f"cannot jit backend '{self.backend}'")
        if "jit" not in self._jit_cache:
            # route compiles through the process-wide CompileLog
            # (obs/compile_log.py) — the serve layer's zero-retrace
            # guarantee is enforced against exactly this wrapper
            self._jit_cache["jit"] = compile_log().instrument(
                jax.jit(self._program()), name=f"{self.name}.jitted",
                kind="jit", arg_names=("params", "inputs"))
        return self._jit_cache["jit"]

    # -- hot swap (the fleet registry's two-phase weight flip) --------------

    def stage_params(self, new_params) -> Dict[Any, Any]:
        """Place ``new_params`` on device for every placement this
        function currently serves, WITHOUT making them live — the
        hot-swap's staging half (sparkdl_tpu/fleet/registry.py). The
        slow transfers happen here, off the dispatch path; the commit
        (:meth:`commit_params`) is then a pointer flip under the serve
        session's swap gate. Returns the staged placements to hand to
        :meth:`commit_params` — or to drop, which un-stages them (the
        rollback path frees the device copies by releasing the only
        reference)."""
        if self.backend != "jax":
            raise ValueError(
                f"cannot stage params for backend {self.backend!r}")
        puts = dict(self._puts) or {"default": jax.device_put}
        staged: Dict[Any, Any] = {}
        log = compile_log()
        for key, put in puts.items():
            t0 = time.perf_counter()
            staged[key] = put(new_params)
            if log.armed:
                leaves = jax.tree_util.tree_leaves(new_params)
                log.record_transfer(
                    name=f"{self.name}.stage_params", kind="device_put",
                    wall_s=time.perf_counter() - t0,
                    detail={"placement": (key if isinstance(key, str)
                                          else key[0]),
                            "leaves": len(leaves),
                            "bytes": sum(int(getattr(v, "nbytes", 0))
                                         for v in leaves)})
        return staged

    def commit_params(self, new_params, staged: Dict[Any, Any]) -> None:
        """Atomically flip to pre-staged params: ``.params`` and every
        device placement change by assignment only — no transfer, no
        retrace (the jit cache is untouched; only argument VALUES
        change, and the compiled shapes were validated by the caller).
        The caller holds the serve session's swap gate so the flip
        lands BETWEEN dispatches, never inside one."""
        self.params = new_params
        self._params_cache = {k: (new_params, v)
                              for k, v in staged.items()}

    def install_aot(self, compiled: Callable, *, wall_s: float = 0.0,
                    blob_bytes: Optional[int] = None) -> Callable:
        """Install a pre-compiled executable behind :meth:`jitted` —
        the executable-import half of the persisted warm-start seam
        (fleet/warmstart.py). The wrapper is the CompileLog's
        :class:`_AotProgram`: dispatches route through it like any
        instrumented program, but nothing it does can ever record a
        compile, because this process only LOADED the program."""
        if self.backend != "jax":
            raise ValueError(
                f"cannot install an executable for backend "
                f"{self.backend!r}")
        wrapper = compile_log().instrument_aot(
            compiled, name=f"{self.name}.jitted", kind="aot",
            wall_s=wall_s,
            detail={"bytes": blob_bytes} if blob_bytes else None)
        self._jit_cache["jit"] = wrapper
        return wrapper

    def __call__(self, inputs, params: Any = "__own__"):
        if self.backend == "host":
            p = self.params if params == "__own__" else params
            d = _as_dict(inputs, self.input_names)
            return self.apply_fn(p, {k: np.asarray(v) for k, v in d.items()})
        p = self.device_params() if params == "__own__" else params
        single = not isinstance(inputs, dict)
        d = _as_dict(inputs, self.input_names)
        d = {k: jnp.asarray(v) for k, v in d.items()}
        # sparkdl-lint: allow[H15] -- jnp.asarray is zero-copy when the caller already hands device (or committed host) arrays, so `d` may ALIAS caller-owned buffers; donating would invalidate the caller's arrays on a second use
        out = self.jitted()(p, d)
        if single and len(out) == 1:
            return next(iter(out.values()))
        return out

    # -- serialization (the "freeze" step) ----------------------------------

    def export(self, batch_size: Optional[int] = None) -> bytes:
        """Serialize to StableHLO bytes with params baked in — the
        TPU-era analogue of ``strip_and_freeze_until`` + GraphDef
        serialization (reference ``graph/utils.py``). ``batch_size=None``
        exports a symbolic batch dimension."""
        if self.backend != "jax":
            raise ValueError(f"cannot export backend '{self.backend}'")
        from jax import export as jax_export

        params = self.params
        base = self.apply_fn

        def frozen(inputs):
            return base(params, inputs)

        if batch_size is None:
            (bdim,) = jax_export.symbolic_shape("batch")
            mk = lambda shape: (bdim,) + tuple(shape)  # noqa: E731
        else:
            mk = lambda shape: (batch_size,) + tuple(shape)  # noqa: E731
        args = {
            n: jax.ShapeDtypeStruct(mk(shape), dtype)
            for n, (shape, dtype) in self.input_signature.items()
        }
        exported = jax_export.export(jax.jit(frozen))(args)
        return bytes(exported.serialize())

    @staticmethod
    def deserialize(blob: bytes, name: str = "stablehlo") -> "ModelFunction":
        """Load serialized StableHLO back into a callable ModelFunction.
        The result is jittable and composable (it re-traces through the
        exported computation)."""
        from jax import export as jax_export
        t0 = time.perf_counter()
        try:
            exported = jax_export.deserialize(blob)
        except Exception as e:
            # jax surfaces raw flatbuffer unpack errors here ("requires
            # a buffer of at least 544501618 bytes") — name the actual
            # problem
            raise ValueError(
                f"not a serialized StableHLO export ({len(blob)} "
                "bytes; produce one with ModelFunction.export / "
                f"ModelIngest.fromExport): {type(e).__name__}: "
                f"{str(e)[:120]}") from e
        # a StableHLO load is a compile-adjacent event the forensics
        # want on the books (obs/compile_log.py): deserialization wall
        # time + blob size, keyed by the deployed name — an AOT
        # warm-start story is judged by where these land relative to
        # the first request
        log = compile_log()
        if log.armed:
            log.record_transfer(
                name=f"{name}.deserialize", kind="deserialize",
                wall_s=time.perf_counter() - t0,
                detail={"bytes": len(blob)})
        in_tree = exported.in_tree
        # input signature from the exported avals: one dict arg
        avals = exported.in_avals
        flat_names = jax.tree.unflatten(in_tree, list(range(len(avals))))
        # flat_names is ((dict_arg,), {}) structure mirror with leaf indices
        (dict_arg,), _ = flat_names
        sig = {}
        for key, idx in dict_arg.items():
            aval = avals[idx]
            sig[key] = (tuple(int(d) for d in aval.shape[1:]), aval.dtype)

        def apply_fn(params_, inputs):
            return exported.call(inputs)

        # Output names AND signature come from the exported avals
        # directly — the lazy eval_shape probe would call the program
        # with batch 1, which a fixed-batch export rejects.
        out_avals = exported.out_avals
        out_tree_names = jax.tree.unflatten(
            exported.out_tree, list(range(len(out_avals))))
        output_names = None
        out_sig = None
        if isinstance(out_tree_names, dict):
            output_names = list(out_tree_names)
            out_sig = {
                key: (tuple(int(d) for d in out_avals[idx].shape[1:]),
                      out_avals[idx].dtype)
                for key, idx in out_tree_names.items()}

        mf = ModelFunction(apply_fn, None, sig, output_names, name=name)
        mf._output_signature = out_sig
        try:
            mf._fixed_batch = int(avals[0].shape[0])
        except Exception:
            # symbolic batch dims (jax shape-poly raises its own
            # InconclusiveDimensionOperation on int()) → no constraint
            mf._fixed_batch = None
        return mf

    # -- shipping -----------------------------------------------------------

    def __getstate__(self):
        """Stage closures holding a ModelFunction ship to Spark
        executors (spark_binding; cloudpickle handles apply_fn and the
        host params pytree). Compiled programs and device-resident
        params are process-local — drop them on the wire; the executor
        re-jits and re-places lazily, exactly like a fresh process.
        Host-backend functions (ingested TF graphs) hold live TF objects
        and cannot ship — re-ingest from the artifact on the executor."""
        if self.backend == "host":
            raise TypeError(
                f"host-backend ModelFunction {self.name!r} cannot be "
                "serialized for shipping (it wraps live TF runtime "
                "state); re-create it on the worker from its source "
                "artifact (SavedModel/checkpoint path), or export a "
                "jax-backend model to StableHLO instead")
        state = self.__dict__.copy()
        state["_jit_cache"] = {}
        state["_params_cache"] = {}
        # put callables may close over meshes / pinned devices —
        # process-local, like the placements they produce
        state["_puts"] = {}
        return state

    def __repr__(self) -> str:
        outs = self._output_names or "?"
        return (f"ModelFunction({self.name}, backend={self.backend}, "
                f"inputs={self.input_names}, outputs={outs})")
