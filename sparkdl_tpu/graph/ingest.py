"""ModelIngest: uniform model ingestion from every supported source.

Re-design of the reference's single most important L4 component,
``python/sparkdl/graph/input.py::TFInputGraph`` — which ingested a TF
model from 6 sources (graph / graphdef / saved_model ±signature /
checkpoint ±signature) into one frozen, serialized form plus tensor-name
mappings. The TPU-era source matrix:

==============================  ============================================
reference source                TPU-native source
==============================  ============================================
tf.Graph in a session           ``fromGraph`` (host-executed, frozen graph);
                                jax users: ``fromFunction`` (fn + params)
frozen GraphDef bytes           ``fromGraphDef`` (host-executed);
                                TPU broadcast form: ``fromExport``
                                (serialized StableHLO bytes)
Keras .h5 model file            ``fromKerasFile`` / ``fromKerasModel``
                                (Keras 3, JAX backend → jittable)
SavedModel + signature          ``fromSavedModelWithSignature``
SavedModel (default sig)        ``fromSavedModel``
tf.train checkpoint (±sig)      ``fromCheckpoint`` / weight-pytree pairing
==============================  ============================================

Honest execution boundary (SURVEY §7 "hard parts"): arbitrary TF-era
graphs (SavedModel/checkpoint meta-graphs) cannot be re-targeted to TPU
without a TF→StableHLO bridge, so they run on the **host CPU via the TF
runtime** — which is exactly where the reference executed them (executor
CPUs via TensorFrames/JNI libtensorflow). They are first-class citizens
of the pipeline (host-backend ModelFunctions); for TPU execution, bring
the model as a jax/flax function, a Keras 3 model, or exported StableHLO,
or extract checkpoint weights into a zoo architecture.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.graph.function import ModelFunction, Signature

_TF_ATTR_SUFFIX = "/.ATTRIBUTES/VARIABLE_VALUE"


def _tf():
    """Import TF lazily, pinned to host CPU (TF is used only to
    read/execute TF-era artifacts; the accelerator belongs to JAX)."""
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    import tensorflow as tf
    try:
        tf.config.set_visible_devices([], "TPU")
        tf.config.set_visible_devices([], "GPU")
    except Exception:
        pass
    return tf


class ModelIngest:
    """Namespace of ingestion constructors; every method returns a
    :class:`ModelFunction` ready for the transformers/runner."""

    # -- native jax sources -------------------------------------------------

    @staticmethod
    def fromFunction(fn: Callable, params: Any = None,
                     input_signature: Optional[Signature] = None,
                     input_shape: Optional[Tuple[int, ...]] = None,
                     input_dtype=np.float32,
                     name: str = "jax_fn") -> ModelFunction:
        """A jax function: either ``fn(params, inputs_dict)->outputs_dict``
        with an explicit ``input_signature``, or a single-tensor
        ``fn(params, x)``/``fn(x)`` with ``input_shape``."""
        if input_signature is not None:
            return ModelFunction(fn, params, input_signature, name=name)
        if input_shape is None:
            raise ValueError("need input_signature or input_shape")
        return ModelFunction.fromSingle(
            fn, params, input_shape=input_shape, input_dtype=input_dtype,
            name=name)

    @staticmethod
    def fromExport(blob: bytes, name: str = "stablehlo") -> ModelFunction:
        """Serialized StableHLO (from ``ModelFunction.export``) — the
        broadcast/frozen form (reference: frozen GraphDef bytes)."""
        return ModelFunction.deserialize(blob, name=name)

    # -- Keras sources ------------------------------------------------------

    @staticmethod
    def fromKerasModel(model, name: Optional[str] = None) -> ModelFunction:
        """A Keras 3 model (JAX backend): wrapped via ``stateless_call``
        so it is a pure jittable function with an explicit params pytree
        (reference: Keras model → frozen graph inside ``KSessionWrap``)."""
        import keras
        if keras.backend.backend() != "jax":
            raise RuntimeError(
                "Keras must run with the JAX backend for TPU execution; "
                "set KERAS_BACKEND=jax before importing keras")
        if len(model.inputs) != 1:
            raise ValueError(
                f"expected a single-input model, got {len(model.inputs)}")
        raw_shape = model.inputs[0].shape[1:]
        if any(d is None for d in raw_shape):
            raise ValueError(
                f"model {model.name!r} has dynamic input shape "
                f"{model.inputs[0].shape}; XLA needs static shapes — "
                "rebuild the model with concrete input dims "
                "(e.g. Input((224, 224, 3)) instead of Input((None, None, 3)))")
        in_shape = tuple(int(d) for d in raw_shape)
        in_dtype = model.inputs[0].dtype or "float32"
        out_names = [f"output_{i}" for i in range(len(model.outputs))]

        params = {
            "trainable": [v.value for v in model.trainable_variables],
            "non_trainable": [v.value for v in model.non_trainable_variables],
        }

        def apply_fn(p, inputs):
            (x,) = inputs.values()
            outs, _ = model.stateless_call(
                p["trainable"], p["non_trainable"], x, training=False)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            return dict(zip(out_names, outs))

        return ModelFunction(
            apply_fn, params,
            input_signature={"input": (in_shape, np.dtype(in_dtype))},
            output_names=out_names,
            name=name or f"keras:{model.name}")

    @staticmethod
    def fromKerasFile(path: str, name: Optional[str] = None) -> ModelFunction:
        """Load a user Keras model file (.h5 legacy or .keras) with the
        JAX backend (reference ``KerasImageFileTransformer.modelFile``)."""
        import keras
        model = keras.models.load_model(path, compile=False)
        return ModelIngest.fromKerasModel(
            model, name=name or f"keras:{os.path.basename(path)}")

    # -- TF-era sources (host-executed; see module docstring) ---------------

    @staticmethod
    def fromGraphDef(graph_def, feed_names: Sequence[str],
                     fetch_names: Sequence[str],
                     name: Optional[str] = None) -> ModelFunction:
        """Frozen TF GraphDef (proto or serialized bytes, the TF1-era
        artifact format) → host-backend ModelFunction executing the
        pruned graph on CPU via the TF runtime, exactly like the
        SavedModel path (reference ``TFInputGraph.fromGraphDef``).

        ``feed_names``/``fetch_names`` are tensor names (``"x:0"``; a
        bare op name means its output 0). Input/output keys on the
        resulting ModelFunction are the clean op names; when several
        tensors come off the SAME op (``"split:0"``, ``"split:1"``)
        their keys keep the output index so none collide — use
        ``rename_io`` to remap.
        """
        tf = _tf()
        if isinstance(graph_def, (bytes, bytearray)):
            proto = tf.compat.v1.GraphDef()
            proto.ParseFromString(bytes(graph_def))
            graph_def = proto

        def _tensor_name(n: str) -> str:
            return n if ":" in n else n + ":0"

        def _import():
            tf.compat.v1.import_graph_def(graph_def, name="")

        wrapped = tf.compat.v1.wrap_function(_import, [])
        feeds = [wrapped.graph.get_tensor_by_name(_tensor_name(n))
                 for n in feed_names]
        fetches = [wrapped.graph.get_tensor_by_name(_tensor_name(n))
                   for n in fetch_names]
        pruned = wrapped.prune(feeds=feeds, fetches=fetches)

        def _keys(names: Sequence[str], role: str) -> List[str]:
            """Dict keys for tensors: the bare op name, EXCEPT when
            several requested tensors share an op — then every such key
            keeps its output index (``op_1``), because colliding keys
            would silently drop all but the last tensor. If even those
            collide with another requested op's literal name (an op
            actually named ``split_0`` next to ``split:0``), fall back
            to the full unique tensor names for everything."""
            full = [_tensor_name(n) for n in names]
            if len(set(full)) != len(full):
                dup = next(t for t in full if full.count(t) > 1)
                raise ValueError(
                    f"duplicate {role} tensor {dup!r}")
            ops = [t.split(":")[0] for t in full]
            keys = [op if ops.count(op) == 1
                    else f"{op}_{t.split(':')[1]}"
                    for op, t in zip(ops, full)]
            return keys if len(set(keys)) == len(keys) else full

        in_keys = _keys(feed_names, "feed")
        out_keys = _keys(fetch_names, "fetch")
        input_signature: Signature = {}
        for key, t in zip(in_keys, feeds):
            shape = tuple(int(d) if d is not None else None
                          for d in t.shape.as_list()[1:]) \
                if t.shape.rank is not None else ()
            input_signature[key] = (shape, np.dtype(t.dtype.name))

        def apply_fn(_params, inputs: Dict[str, np.ndarray]):
            args = [tf.constant(np.asarray(inputs[k])) for k in in_keys]
            out = pruned(*args)
            if not isinstance(out, (list, tuple)):
                out = [out]
            return {k: np.asarray(v) for k, v in zip(out_keys, out)}

        mf = ModelFunction(
            apply_fn, params=None, input_signature=input_signature,
            output_names=out_keys, backend="host",
            name=name or "graphdef")
        mf._keras_loaded = pruned  # keep the ConcreteFunction alive
        return mf

    @staticmethod
    def fromGraph(graph, feed_names: Sequence[str],
                  fetch_names: Sequence[str],
                  name: Optional[str] = None) -> ModelFunction:
        """A live ``tf.Graph`` (frozen: variables already constants) →
        host-backend ModelFunction (reference ``TFInputGraph.fromGraph``,
        which froze the session's graph; freeze first if yours holds
        variables)."""
        return ModelIngest.fromGraphDef(
            graph.as_graph_def(), feed_names, fetch_names,
            name=name or "graph")

    @staticmethod
    def fromSavedModel(saved_model_dir: str,
                       tagSet: Optional[str] = None,
                       signatureDefKey: Optional[str] = None,
                       name: Optional[str] = None) -> ModelFunction:
        """TF SavedModel → host-backend ModelFunction executing the chosen
        signature on CPU via the TF runtime (reference
        ``TFInputGraph.fromSavedModel``)."""
        tf = _tf()
        tags = tagSet.split(",") if tagSet else None
        loaded = tf.saved_model.load(saved_model_dir, tags=tags)
        key = signatureDefKey or "serving_default"
        if key not in loaded.signatures:
            raise KeyError(
                f"signature {key!r} not in SavedModel; available: "
                f"{list(loaded.signatures)}")
        sig_fn = loaded.signatures[key]

        _, kw_specs = sig_fn.structured_input_signature
        input_signature: Signature = {}
        for arg_name, spec in kw_specs.items():
            # dynamic (None) non-batch dims are legal in serving
            # signatures; keep them as None — the host path never needs
            # static shapes (only jax-backend functions do).
            shape = tuple(int(d) if d is not None else None
                          for d in spec.shape[1:])
            input_signature[arg_name] = (shape, np.dtype(spec.dtype.name))
        out_names = list(sig_fn.structured_outputs)

        def apply_fn(_params, inputs: Dict[str, np.ndarray]):
            feed = {k: tf.constant(np.asarray(v)) for k, v in inputs.items()}
            out = sig_fn(**feed)
            return {k: np.asarray(v) for k, v in out.items()}

        mf = ModelFunction(
            apply_fn, params=None, input_signature=input_signature,
            output_names=out_names, backend="host",
            name=name or f"saved_model:{os.path.basename(saved_model_dir)}")
        mf._keras_loaded = loaded  # keep the trackable alive
        return mf

    @staticmethod
    def fromSavedModelWithSignature(saved_model_dir: str,
                                    signatureDefKey: str,
                                    name: Optional[str] = None
                                    ) -> ModelFunction:
        """Explicit-signature variant (reference
        ``fromSavedModelWithSignature``)."""
        return ModelIngest.fromSavedModel(
            saved_model_dir, signatureDefKey=signatureDefKey, name=name)

    @staticmethod
    def loadCheckpointVariables(checkpoint_path: str) -> Dict[str, np.ndarray]:
        """Read all variables from a TF checkpoint (dir or file prefix)
        into ``{clean_name: ndarray}`` — TF2 object-graph attribute
        suffixes are stripped. This is the weight-extraction half of the
        reference's ``fromCheckpoint`` freeze."""
        tf = _tf()
        path = checkpoint_path
        if os.path.isdir(path):
            latest = tf.train.latest_checkpoint(path)
            if latest is None:
                raise FileNotFoundError(
                    f"no checkpoint found under {path}")
            path = latest
        reader = tf.train.load_checkpoint(path)
        out = {}
        for key in reader.get_variable_to_shape_map():
            if key == "_CHECKPOINTABLE_OBJECT_GRAPH":
                continue
            clean = key[:-len(_TF_ATTR_SUFFIX)] \
                if key.endswith(_TF_ATTR_SUFFIX) else key
            out[clean] = reader.get_tensor(key)
        return out

    @staticmethod
    def fromCheckpoint(checkpoint_path: str,
                       apply_fn: Callable,
                       input_signature: Signature,
                       params_builder: Optional[
                           Callable[[Dict[str, np.ndarray]], Any]] = None,
                       name: Optional[str] = None) -> ModelFunction:
        """TF checkpoint + a jax ``apply_fn`` → TPU-native ModelFunction.

        ``params_builder`` maps the checkpoint's ``{name: ndarray}`` to
        the pytree ``apply_fn`` expects (defaults to the dict itself).
        This is the TPU-honest version of the reference's
        ``fromCheckpoint`` (which imported the checkpoint's meta-graph:
        impossible to retarget to XLA; the *weights* are what survive).
        """
        variables = ModelIngest.loadCheckpointVariables(checkpoint_path)
        params = params_builder(variables) if params_builder else variables
        return ModelFunction(
            apply_fn, params, input_signature,
            name=name or f"checkpoint:{os.path.basename(checkpoint_path)}")

    @staticmethod
    def fromCheckpointWithSignature(checkpoint_path: str,
                                    apply_fn: Callable,
                                    input_signature: Signature,
                                    input_mapping: Dict[str, str],
                                    output_mapping: Dict[str, str],
                                    params_builder=None,
                                    name: Optional[str] = None
                                    ) -> ModelFunction:
        """Checkpoint variant with signature-name translation (reference
        ``fromCheckpointWithSignature`` + ``translateInput/OutputMapping``)."""
        mf = ModelIngest.fromCheckpoint(
            checkpoint_path, apply_fn, input_signature,
            params_builder=params_builder, name=name)
        return mf.rename_io(input_mapping, output_mapping)


# Reference-era alias: sparkdl users know this class as TFInputGraph.
TFInputGraph = ModelIngest
