"""KerasImageFileEstimator: parallel hyperparameter search + DP fine-tune.

Re-design of the reference's only Estimator
(``python/sparkdl/estimators/keras_image_file_estimator.py``). The
reference's ``fit(df, paramMaps)``: collect (URI, label) rows to the
driver, decode EVERY image on the driver with ``imageLoader``, broadcast
``(X, y)``, then run one Spark task per ParamMap, each deserializing the
Keras ``.h5`` and running single-machine ``model.fit`` (SURVEY §3.4).
Its two scalability cliffs — driver-serial decode and single-machine
training — are exactly what the TPU re-design removes:

* decode runs batch-parallel on engine host threads
  (``CanLoadImage.loadImagesInternal``), not serially on the driver;
* each trial's train step is a pure jax/optax loop over the Keras-3
  model's ``stateless_call``, jitted **against a device mesh** with the
  batch split over the ``data`` axis and params replicated — XLA inserts
  the gradient all-reduce over ICI (the north-star pjit DP fine-tune;
  the reference had NO gradient sync anywhere, SURVEY §2.4).

Task-parallel HPO is preserved: ``fitMultiple`` runs trials concurrently
on a thread pool (the analogue of one-Spark-task-per-ParamMap), each
trial loading its own copy of the model file just as each Spark task
deserialized its own ``.h5``.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.data.frame import column_index
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs import span
from sparkdl_tpu.obs.watchdog import watch as watchdog_watch
from sparkdl_tpu.parallel.mesh import collective_launch
from sparkdl_tpu.params import (
    CanLoadImage,
    HasBatchSize,
    HasInputCol,
    HasKerasLoss,
    HasKerasModel,
    HasKerasOptimizer,
    HasLabelCol,
    HasOutputCol,
    HasOutputMode,
    HasUseMesh,
    keyword_only,
)
from sparkdl_tpu.params.base import Param, TypeConverters
from sparkdl_tpu.params.pipeline import Estimator, Model
from sparkdl_tpu.runtime.runner import RunnerMetrics

_LOADED_COL = "__sparkdl_tpu_loaded__"


# ---------------------------------------------------------------------------
# loss / optimizer resolution (reference: kerasLoss / kerasOptimizer params,
# param/__init__.py::toKerasLoss / toKerasOptimizer converters)
# ---------------------------------------------------------------------------

_EPS = 1e-7


def _resolve_loss(loss) -> Callable:
    """Loss name/callable → ``fn(preds, targets) -> [N] losses``.

    Keras-era names keep Keras semantics (probabilities in, like the
    reference's compiled Keras losses); other strings resolve to optax
    losses of the same name (logits in, per optax convention).
    """
    import jax.numpy as jnp
    import optax

    if callable(loss):
        return loss
    if loss == "categorical_crossentropy":
        return lambda p, y: -jnp.sum(
            y * jnp.log(jnp.clip(p, _EPS, 1.0)), axis=-1)
    if loss == "binary_crossentropy":
        return lambda p, y: -jnp.mean(
            y * jnp.log(jnp.clip(p, _EPS, 1.0))
            + (1.0 - y) * jnp.log(jnp.clip(1.0 - p, _EPS, 1.0)), axis=-1)
    if loss == "mse":
        return lambda p, y: jnp.mean(jnp.square(p - y), axis=-1)
    fn = getattr(optax, loss, None)
    if fn is None:
        raise ValueError(f"unknown loss {loss!r}")
    return fn


def _config_fingerprint_bytes(est) -> bytes:
    """Hyperparameter identity for checkpoint fingerprints. ``epochs``
    is deliberately EXCLUDED: it is the training budget, not the run's
    identity — an interrupted 2-epoch run extended to 4 epochs must
    resume the same checkpoints, not start a fresh directory."""
    fit_params = {k: v for k, v in est.getKerasFitParams().items()
                  if k != "epochs"}
    # field SEPARATORS matter: delimiter-free concatenation lets
    # distinct configs collide byte-for-byte and silently share a
    # checkpoint directory
    return "\x1f".join([
        repr(sorted(fit_params.items())),
        repr(est.getKerasLoss()),
        repr(est.getOrDefault("kerasOptimizer")),
        est.getModelFile(),
    ]).encode()


def _make_step(model, loss_fn, tx):
    """One SGD step over a static-shape batch (shared by the in-memory
    and streaming trainers)."""
    import jax
    import jax.numpy as jnp

    def step(trainable, non_trainable, opt_state, xb, yb):
        def scalar_loss(tr):
            preds, new_nt = model.stateless_call(
                tr, non_trainable, xb, training=True)
            if isinstance(preds, (list, tuple)):
                preds = preds[0]
            return jnp.mean(loss_fn(preds, yb)), new_nt

        (loss, new_nt), grads = jax.value_and_grad(
            scalar_loss, has_aux=True)(trainable)
        updates, opt_state2 = tx.update(grads, opt_state, trainable)
        return (jax.tree.map(lambda p, u: p + u, trainable, updates),
                new_nt, opt_state2, loss)

    return step


def _resolve_optimizer(opt, fit_params: dict):
    """Optimizer name/transform → optax GradientTransformation."""
    import optax

    if isinstance(opt, optax.GradientTransformation):
        return opt
    lr = float(fit_params.get("learning_rate", 1e-3))
    return getattr(optax, opt)(lr)


# ---------------------------------------------------------------------------
# the fitted model
# ---------------------------------------------------------------------------

class KerasImageFileModel(Model, HasInputCol, HasOutputCol, HasOutputMode,
                          HasBatchSize, HasUseMesh, CanLoadImage):
    """Fitted model: trained weights wrapped as a ModelFunction.

    Plays the role of the ``KerasImageFileTransformer`` the reference
    built from each trial's returned weight bytes (reference
    ``_collectModels``): transform = imageLoader on host threads →
    jitted forward on device.
    """

    def __init__(self, model_fn: ModelFunction, *, inputCol, outputCol,
                 imageLoader, outputMode="vector", batchSize=64,
                 useMesh=False, history: Optional[List[float]] = None,
                 resumedFrom: int = 0):
        super().__init__()
        self._setDefault(outputMode="vector", batchSize=64, useMesh=False)
        self._set(inputCol=inputCol, outputCol=outputCol,
                  imageLoader=imageLoader, outputMode=outputMode,
                  batchSize=batchSize, useMesh=useMesh)
        self.modelFunction = model_fn
        self.history = history or []  # per-epoch mean training loss
        # which epoch this fit restored from (0 = trained from scratch)
        # — observable proof a checkpointDir resume actually happened
        self.resumedFrom = int(resumedFrom)
        self.metrics = RunnerMetrics()

    def _transform(self, dataset):
        import pyarrow as pa

        from sparkdl_tpu.transformers import utils as tfr_utils

        mf = self.modelFunction
        in_name, out_name = tfr_utils.single_io(mf)
        out_col = self.getOutputCol()
        mode = self.getOutputMode()
        from sparkdl_tpu.transformers.utils import make_runner
        runner = make_runner(mf, self.getBatchSize(),
                             use_mesh=self.getUseMesh(),
                             metrics=self.metrics)
        loaded = self.loadImagesInternal(dataset, self.getInputCol(),
                                         _LOADED_COL)

        def apply(batch: pa.RecordBatch) -> pa.RecordBatch:
            from sparkdl_tpu.data.tensors import arrow_to_tensor
            idx = column_index(batch, _LOADED_COL)
            arr = arrow_to_tensor(batch.column(idx),
                                  batch.schema.field(idx))
            shape, dtype = mf.input_signature[in_name]
            arr = tfr_utils.reshapeLoadedRows(arr, shape, dtype, mf.name)
            out = runner.run({in_name: arr})
            batch = batch.remove_column(idx)
            return tfr_utils.appendModelOutput(batch, out_col,
                                               out[out_name], mode)

        return loaded.map_batches(apply, kind="device",
                                  name=f"apply({mf.name})",
                                  batch_hint=runner.preferred_chunk)

    def copy(self, extra: Optional[dict] = None) -> "KerasImageFileModel":
        that = super().copy(extra)
        that.modelFunction = self.modelFunction
        that.history = list(self.history)
        that.metrics = RunnerMetrics()
        return that

    def _extra_state(self):
        # the ModelFunction persists as StableHLO with the trained
        # weights baked in (persistence.py's model_fn codec)
        return {"modelFunction": self.modelFunction,
                "history": [float(v) for v in self.history],
                "resumedFrom": self.resumedFrom}

    @classmethod
    def _from_saved(cls, params, extra, children):
        return cls(extra["modelFunction"],
                   inputCol=params["inputCol"],
                   outputCol=params["outputCol"],
                   imageLoader=params.get("imageLoader"),
                   outputMode=params.get("outputMode", "vector"),
                   batchSize=params.get("batchSize", 64),
                   useMesh=params.get("useMesh", False),
                   history=extra.get("history"),
                   resumedFrom=extra.get("resumedFrom", 0))


# ---------------------------------------------------------------------------
# the estimator
# ---------------------------------------------------------------------------

class KerasImageFileEstimator(Estimator, HasInputCol, HasOutputCol,
                              HasLabelCol, HasKerasModel, HasKerasOptimizer,
                              HasKerasLoss, HasOutputMode, HasBatchSize,
                              CanLoadImage):
    """Fits a user Keras model file on an image-URI DataFrame.

    Params mirror the reference estimator (``inputCol`` URI column,
    ``labelCol``, ``modelFile``, ``imageLoader``, ``kerasOptimizer``,
    ``kerasLoss``, ``kerasFitParams``, ``outputCol``/``outputMode``).
    ``kerasFitParams`` keys: ``epochs`` (default 1), ``batch_size``
    (default 32, the PER-TRAIN-STEP global batch), ``learning_rate``,
    ``shuffle`` (default True), ``seed``.

    ``parallelism`` bounds concurrent trials in ``fitMultiple``;
    ``useMesh`` jits each train step against the local device mesh
    (data-parallel over all chips) instead of single-device.
    """

    parallelism = Param("KerasImageFileEstimator", "parallelism",
                        "max concurrent trials in fitMultiple",
                        TypeConverters.toInt)
    useMesh = Param("KerasImageFileEstimator", "useMesh",
                    "jit train steps data-parallel over the device mesh",
                    TypeConverters.toBoolean)
    checkpointDir = Param(
        "KerasImageFileEstimator", "checkpointDir",
        "orbax checkpoint directory: training state saves per epoch and "
        "an interrupted fit resumes from the last epoch (the reference "
        "restarted from scratch, SURVEY §5)", TypeConverters.toString)
    streaming = Param(
        "KerasImageFileEstimator", "streaming",
        "train by streaming decoded partitions through the engine "
        "instead of collecting (X, y) into driver memory — removes the "
        "reference's dataset-must-fit-in-driver cliff (SURVEY §3.4) at "
        "the cost of re-decoding each epoch (see cacheDecoded)",
        TypeConverters.toBoolean)
    cacheDecoded = Param(
        "KerasImageFileEstimator", "cacheDecoded",
        "streaming mode: spill decoded tensors to per-partition Arrow "
        "files during epoch 1 and stream the cache on later epochs — "
        "JPEG decode runs once per fit instead of once per epoch, "
        "while memory stays streaming-shaped", TypeConverters.toBoolean)

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, labelCol=None,
                 modelFile=None, imageLoader=None, kerasOptimizer="adam",
                 kerasLoss="categorical_crossentropy", kerasFitParams=None,
                 outputMode="vector", batchSize=64, parallelism=2,
                 useMesh=True, checkpointDir=None, streaming=False,
                 cacheDecoded=False):
        super().__init__()
        self._setDefault(kerasOptimizer="adam",
                         kerasLoss="categorical_crossentropy",
                         kerasFitParams={"epochs": 1, "batch_size": 32},
                         outputMode="vector", batchSize=64, parallelism=2,
                         useMesh=True, streaming=False, cacheDecoded=False)
        self._set(inputCol=inputCol, outputCol=outputCol, labelCol=labelCol,
                  modelFile=modelFile, imageLoader=imageLoader,
                  kerasOptimizer=kerasOptimizer, kerasLoss=kerasLoss,
                  kerasFitParams=kerasFitParams, outputMode=outputMode,
                  batchSize=batchSize, parallelism=parallelism,
                  useMesh=useMesh, checkpointDir=checkpointDir,
                  streaming=streaming, cacheDecoded=cacheDecoded)

    # -- validation (reference _validateParams) -----------------------------

    def _validateParams(self):
        for name in ("inputCol", "outputCol", "labelCol", "modelFile",
                     "imageLoader"):
            if not self.isDefined(name):
                raise ValueError(f"KerasImageFileEstimator requires param "
                                 f"{name!r} to be set")

    # -- data localization (reference _getNumpyFeaturesAndLabels) -----------

    def _getNumpyFeaturesAndLabels(self, dataset
                                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Decode the URI column with ``imageLoader`` on engine host
        threads and collect ``(X, y)`` (the reference decoded serially on
        the driver — its documented scalability cliff)."""
        self._validateParams()
        loaded = self.loadImagesInternal(
            dataset.select(self.getInputCol(), self.getLabelCol()),
            self.getInputCol(), _LOADED_COL)
        table = loaded.collect()
        from sparkdl_tpu.data.tensors import arrow_to_tensor
        idx = column_index(table, _LOADED_COL)
        X = np.asarray(arrow_to_tensor(table.column(idx),
                                       table.schema.field(idx)),
                       dtype=np.float32)
        y = np.asarray(table.column(column_index(table, self.getLabelCol()))
                       .to_pylist())
        return X, y

    # -- one trial ----------------------------------------------------------

    @staticmethod
    def _trial_fingerprint(est, X: np.ndarray, y: np.ndarray) -> str:
        """Checkpoint identity for one trial: hyperparameters AND data.
        Resume must only ever continue a run with the same config on the
        same (X, y) — CrossValidator folds and different param maps get
        distinct fingerprints, so they can never adopt each other's
        weights."""
        import hashlib
        h = hashlib.sha256()
        h.update(_config_fingerprint_bytes(est))
        h.update(repr((X.shape, str(X.dtype))).encode())
        h.update(np.ascontiguousarray(y).tobytes())
        stride = max(1, len(X) // 16)
        h.update(np.ascontiguousarray(X[::stride]).tobytes())
        return h.hexdigest()[:16]

    def _setup_trial(self):
        """Load the trial's own model copy (reference: each Spark task
        deserialized the .h5, so concurrent trials never share state)
        and build loss/optimizer/initial state."""
        import keras

        if keras.backend.backend() != "jax":
            raise RuntimeError("KerasImageFileEstimator requires "
                               "KERAS_BACKEND=jax")
        model = keras.models.load_model(self.getModelFile(), compile=False)
        loss_fn = _resolve_loss(self.getKerasLoss())
        tx = _resolve_optimizer(self.getKerasOptimizer(),
                                self.getKerasFitParams())
        trainable = [v.value for v in model.trainable_variables]
        non_trainable = [v.value for v in model.non_trainable_variables]
        opt_state = tx.init(trainable)
        return model, loss_fn, tx, trainable, non_trainable, opt_state

    def _trainOne(self, X: np.ndarray, y: np.ndarray, paramMap: dict,
                  checkpoint_tag: str = "fit") -> KerasImageFileModel:
        """Train one configuration with a pure jax/optax loop (the
        reference ran ``model.fit`` on one machine per Spark task).
        With ``checkpointDir`` set, state saves each epoch (async) under
        ``dir/<tag>_<fingerprint>`` and a re-run with the same config
        and data resumes at the last saved epoch, producing the same
        final model as an uninterrupted run."""
        import jax
        import jax.numpy as jnp

        est = self.copy(paramMap) if paramMap else self
        est._validateParams()
        fit_params = est.getKerasFitParams()
        epochs = int(fit_params.get("epochs", 1))
        batch_size = int(fit_params.get("batch_size", 32))
        shuffle = bool(fit_params.get("shuffle", True))
        seed = int(fit_params.get("seed", 0))

        model, loss_fn, tx, trainable, non_trainable, opt_state = \
            est._setup_trial()
        n_out = int(model.outputs[0].shape[-1])
        targets = self._prepare_targets(y, est.getKerasLoss(), n_out)

        step = _make_step(model, loss_fn, tx)
        jitted, batch_size, mesh = est._compile_step(step, batch_size)
        # the step's gradient all-reduce makes this a collective
        # program: concurrent trials must not interleave their
        # per-device launches (parallel/mesh.py::collective_launch)
        launch = collective_launch(mesh)

        n = len(X)
        if n == 0:
            raise ValueError("cannot fit on an empty dataset")
        steps_per_epoch = max(1, math.ceil(n / batch_size))
        rng = np.random.default_rng(seed)
        history: List[float] = []

        checkpointer = None
        start_epoch = 0
        if est.isDefined("checkpointDir"):
            import os as _os

            from sparkdl_tpu.parallel.checkpoint import PytreeCheckpointer
            trial_dir = _os.path.join(
                est.getOrDefault("checkpointDir"),
                f"{checkpoint_tag}_{self._trial_fingerprint(est, X, y)}")
            checkpointer = PytreeCheckpointer(trial_dir)
            # resume from the newest step still on disk that fits this
            # run's epoch budget (older steps may have been pruned)
            usable = [s for s in checkpointer.all_steps() if s <= epochs]
            if usable:
                start_epoch = max(usable)
                template = {"trainable": trainable,
                            "non_trainable": non_trainable,
                            "opt_state": opt_state,
                            "history": np.zeros(start_epoch, np.float64)}
                restored = checkpointer.restore(template, step=start_epoch)
                trainable = restored["trainable"]
                non_trainable = restored["non_trainable"]
                opt_state = restored["opt_state"]
                history = [float(h) for h in restored["history"]]
                # burn the skipped epochs' shuffles so a resumed run
                # sees the same batch order as an uninterrupted one
                for _ in range(start_epoch):
                    if shuffle:
                        rng.permutation(n)

        for epoch in range(start_epoch, epochs):
            with span("epoch", lane="estimator", epoch=epoch):
                order = rng.permutation(n) if shuffle else np.arange(n)
                # wrap indices so every step sees a full static-shape
                # batch (XLA: no dynamic shapes; a padded+masked tail
                # costs more than repeating a few rows at epoch
                # boundaries); np.resize tiles the permutation as often
                # as needed when batch_size > n
                if n % batch_size:
                    order = np.resize(order,
                                      steps_per_epoch * batch_size)
                losses = []
                for s in range(steps_per_epoch):
                    sel = order[s * batch_size:(s + 1) * batch_size]
                    # stage the batch OUTSIDE the launch lock (the lock
                    # covers only the collective program's dispatch, so
                    # concurrent trials overlap host work with it)
                    xb = jnp.asarray(X[sel])
                    yb = jnp.asarray(targets[sel])
                    with span("step", lane="estimator",
                              rows=batch_size), \
                            watchdog_watch("estimator.step"), launch:
                        trainable, non_trainable, opt_state, loss = \
                            jitted(trainable, non_trainable, opt_state,
                                   xb, yb)
                    losses.append(loss)
                # sparkdl-lint: allow[H1] -- epoch-boundary drain: the
                # epoch's async step chain must land before loss history
                history.append(float(np.mean(jax.device_get(losses))))
                if checkpointer is not None:
                    checkpointer.save(
                        len(history),
                        # sparkdl-lint: allow[H1] -- checkpoint snapshot:
                        # saved state must be host bytes, synced at the
                        # epoch boundary (not on the step path)
                        {"trainable": jax.device_get(trainable),  # sparkdl-lint: allow[H1] -- checkpoint snapshot
                         "non_trainable": jax.device_get(non_trainable),  # sparkdl-lint: allow[H1] -- checkpoint snapshot
                         "opt_state": jax.device_get(opt_state),  # sparkdl-lint: allow[H1] -- checkpoint snapshot
                         "history": np.asarray(history, np.float64)})
        if checkpointer is not None:
            checkpointer.close()

        trained = {
            # sparkdl-lint: allow[H1] -- end-of-fit drain: the trained
            # weights leave the device exactly once, here
            "trainable": jax.device_get(trainable),  # sparkdl-lint: allow[H1] -- end-of-fit drain
            "non_trainable": jax.device_get(non_trainable),  # sparkdl-lint: allow[H1] -- end-of-fit drain
        }
        mf = self._as_model_function(model, trained)
        return KerasImageFileModel(
            mf, inputCol=est.getInputCol(), outputCol=est.getOutputCol(),
            imageLoader=est.getImageLoader(), outputMode=est.getOutputMode(),
            batchSize=est.getBatchSize(),
            useMesh=est.getOrDefault("useMesh"), history=history,
            resumedFrom=start_epoch)

    def _compile_step(self, step, batch_size: int):
        """jit the train step — against the mesh (batch split over the
        ``data`` axis, state replicated; XLA psums grads over ICI) when
        ``useMesh`` and >1 device, else single-device.

        Nothing is donated. XLA can reuse a donated input only for an
        output of the same shape and dtype, and the step returns the
        state and a scalar loss — so a donated batch ``(xb, yb)`` is
        never usable: on the v5e, as on the CPU, it only produced
        JAX's "Some donated buffers were not usable" warning at every
        compile (chip_smoke.py's fit leg holds that warning at zero).
        The STATE arguments, which do have same-shaped outputs, are
        deliberately NOT donated: the streaming trainer's async
        checkpoint save reads the live ``trainable``/``opt_state``
        arrays between steps.

        Returns ``(jitted, batch_size, mesh)`` — mesh is None on the
        single-device path; callers that place arrays themselves
        (multi-host streaming) derive their shardings from THIS mesh so
        the jit's in_shardings and the placed arrays can never diverge.

        Both branches route through the process-wide compile log
        (obs/compile_log.py): a training loop that starts retracing
        per step (a shape leak in the batch feed) is attributed at
        runtime with a diff naming the argument, instead of
        presenting as an unexplained slowdown.
        """
        import jax

        from sparkdl_tpu.obs.compile_log import compile_log

        step_args = ("trainable", "non_trainable", "opt_state",
                     "xb", "yb")
        if self.getOrDefault("useMesh") and len(jax.devices()) > 1:
            from sparkdl_tpu.parallel.mesh import (
                DATA_AXIS, data_sharding, make_mesh, replicated)
            mesh = make_mesh()
            ndata = mesh.shape[DATA_AXIS]
            batch_size = max(1, -(-batch_size // ndata)) * ndata
            rep, dat = replicated(mesh), data_sharding(mesh)
            jitted = jax.jit(step,
                             in_shardings=(rep, rep, rep, dat, dat),
                             out_shardings=(rep, rep, rep, rep))
            jitted = compile_log().instrument(
                jitted, name=f"{type(self).__name__}.train_step",
                kind="sharded_jit",
                config={"mesh": tuple(mesh.shape.items())},
                arg_names=step_args)
            return jitted, batch_size, mesh
        jitted = jax.jit(step)
        jitted = compile_log().instrument(
            jitted, name=f"{type(self).__name__}.train_step",
            kind="jit", arg_names=step_args)
        return jitted, batch_size, None

    @staticmethod
    def _prepare_targets(y: np.ndarray, loss, n_out: int) -> np.ndarray:
        """Integer class labels one-hot to the model's output width for
        categorical losses — including float64 columns holding INTEGRAL
        class ids, the Spark ML label convention this library accepts
        everywhere else (LogisticRegression, its predictionCol output);
        everything else passes through as float32, with 1-D targets
        lifted to [N, 1] so elementwise losses align with a 2-D model
        output — without the reshape, [N,1] preds against [N] targets
        broadcast to [N,N] and BCE silently minimizes a wrong
        objective."""
        if loss == "categorical_crossentropy" and y.ndim == 1:
            ids = None
            if np.issubdtype(y.dtype, np.integer):
                ids = y.astype(np.int64)
            elif (np.issubdtype(y.dtype, np.floating) and len(y)
                    and (y == np.round(y)).all()):
                ids = y.astype(np.int64)
            if ids is not None:
                if len(ids) and (ids.min() < 0 or ids.max() >= n_out):
                    # np.eye fancy-indexing would silently WRAP a -1
                    # label to the last class (re-encode {-1,1} to
                    # {0,1}, like LogisticRegression demands)
                    raise ValueError(
                        f"class ids must be in [0, {n_out}); got range "
                        f"[{ids.min()}, {ids.max()}] (re-encode e.g. "
                        "{-1,1} labels to {0,1})")
                return np.eye(n_out, dtype=np.float32)[ids]
        y = np.asarray(y, dtype=np.float32)
        if y.ndim == 1:
            y = y.reshape(len(y), 1)
            if n_out != 1:
                raise ValueError(
                    f"1-D targets against a {n_out}-wide model output; "
                    "provide targets shaped [N, n_out] explicitly")
        return y

    @staticmethod
    def _as_model_function(model, trained: Dict[str, Any]) -> ModelFunction:
        """Trained weights + the loaded Keras model → inference
        ModelFunction (same wrapping as ``ModelIngest.fromKerasModel``,
        with the trial's weights instead of the file's)."""
        raw_shape = model.inputs[0].shape[1:]
        if any(d is None for d in raw_shape):
            raise ValueError(
                f"model {model.name!r} has dynamic input shape; XLA needs "
                "static shapes")
        in_shape = tuple(int(d) for d in raw_shape)
        in_dtype = model.inputs[0].dtype or "float32"
        out_names = [f"output_{i}" for i in range(len(model.outputs))]

        def apply_fn(p, inputs):
            (x,) = inputs.values()
            outs, _ = model.stateless_call(
                p["trainable"], p["non_trainable"], x, training=False)
            if not isinstance(outs, (list, tuple)):
                outs = [outs]
            return dict(zip(out_names, outs))

        return ModelFunction(
            apply_fn, trained,
            input_signature={"input": (in_shape, np.dtype(in_dtype))},
            output_names=out_names,
            name=f"keras_trained:{model.name}")

    # -- streaming training --------------------------------------------------

    @staticmethod
    def _streaming_fingerprint(est, uris, labels) -> str:
        """Checkpoint identity for a streaming trial: hyperparameters
        AND the (uri, label) manifest — images themselves are never
        materialized whole, so the manifest stands in for the data."""
        import hashlib
        h = hashlib.sha256()
        h.update(_config_fingerprint_bytes(est))
        for u, l in zip(uris, labels):
            # separators: 'img1',23 must not hash like 'img12',3
            h.update(str(u).encode() + b"\x1f")
            h.update(repr(l).encode() + b"\x1e")
        return h.hexdigest()[:16]

    def _epoch_stream(self, loaded, label_col, batch_size,
                      n_out, loss, epoch_seed, shuffle,
                      num_steps: Optional[int] = None):
        """Yield uniform (xb, yb) training batches from the loaded
        frame's partition stream, one epoch's worth.

        Partition order is permuted per epoch (shuffle) and rows are
        permuted within each partition — an engine-friendly shuffle that
        never holds more than a partition plus one batch in memory. A
        partial final batch is filled cyclically from the epoch's first
        rows, matching the in-memory trainer's np.resize(order) wrap so
        every step sees a full static-shape batch.

        ``num_steps``: yield EXACTLY this many batches (multi-host mode:
        every host must take the same number of steps or the collective
        deadlocks) — the stream restarts over the frame if this host's
        shard runs dry before the quota, and stops early once met.
        ``None`` (single-host) derives the step count from the data.
        """
        import collections

        from sparkdl_tpu.data.frame import column_index
        from sparkdl_tpu.data.tensors import arrow_to_tensor

        rng = np.random.default_rng(epoch_seed)
        frame = loaded
        if shuffle:
            frame = loaded.with_partition_order(
                rng.permutation(loaded.num_partitions))

        # (xs, ys, offset) segments; emitting a batch slices views and
        # copies exactly batch_size rows — never the whole remainder
        parts: collections.deque = collections.deque()
        buffered = 0
        emitted = 0
        head_x = head_y = None  # first batch, kept for the cyclic tail

        def targets(y):
            return self._prepare_targets(np.asarray(y), loss, n_out)

        def emit(n_rows: int):
            nonlocal buffered
            xs_out, ys_out = [], []
            need = n_rows
            while need:
                xs, ys, off = parts[0]
                take = min(need, len(xs) - off)
                xs_out.append(xs[off:off + take])
                ys_out.append(ys[off:off + take])
                if off + take == len(xs):
                    parts.popleft()
                else:
                    parts[0] = (xs, ys, off + take)
                need -= take
            buffered -= n_rows
            return np.concatenate(xs_out), np.concatenate(ys_out)

        def tail_batch():
            """Assemble the final partial batch, wrapped cyclically."""
            X, y = emit(buffered)
            if head_x is None:
                # whole pass smaller than one batch: tile it (the
                # in-memory trainer's np.resize does the same)
                reps = -(-batch_size // len(X))
                X = np.concatenate([X] * reps)[:batch_size]
                y = np.concatenate([y] * reps)[:batch_size]
            else:
                pad = batch_size - len(X)
                X = np.concatenate([X, head_x[:pad]])
                y = np.concatenate([y, head_y[:pad]])
            return X, y

        while True:
            saw_rows = False
            for batch in frame.stream():
                idx = column_index(batch, _LOADED_COL)
                xs = np.asarray(arrow_to_tensor(batch.column(idx),
                                                batch.schema.field(idx)),
                                dtype=np.float32)
                ys = np.asarray(
                    batch.column(column_index(batch, label_col))
                    .to_pylist())
                if shuffle and len(xs) > 1:
                    perm = rng.permutation(len(xs))
                    xs, ys = xs[perm], ys[perm]
                if len(xs):
                    saw_rows = True
                    parts.append((xs, ys, 0))
                    buffered += len(xs)
                while buffered >= batch_size and (
                        num_steps is None or emitted < num_steps):
                    xb, yb = emit(batch_size)
                    if head_x is None:
                        head_x, head_y = xb, yb
                    emitted += 1
                    yield xb, targets(yb)
                if num_steps is not None and emitted >= num_steps:
                    return
            # one full pass over the frame is done
            if num_steps is None:
                if buffered:
                    X, y = tail_batch()
                    yield X, targets(y)
                return
            if emitted >= num_steps:
                return
            if not saw_rows and not buffered and head_x is None:
                raise ValueError(
                    "this host's data shard is empty; repartition the "
                    "dataset with at least one partition per host "
                    "(numPartitions >= process_count)")
            if buffered:
                X, y = tail_batch()
                emitted += 1
                yield X, targets(y)
                if emitted >= num_steps:
                    return
            # shard dry, quota unmet: stream it again (re-decode)

    def _trainStreaming(self, dataset, paramMap: dict,
                        checkpoint_tag: str = "fit",
                        spill_dir: Optional[str] = None
                        ) -> KerasImageFileModel:
        """Entry for one streaming trial: resolves the effective
        estimator and owns the decoded-spill directory's lifetime
        (created here when ``cacheDecoded`` and none was passed, removed
        on ANY exit — early validation failures included). A caller
        passing ``spill_dir`` (fitMultiple's shared trial cache) keeps
        ownership."""
        est = self.copy(paramMap) if paramMap else self
        if not est.getOrDefault("cacheDecoded"):
            spill_dir = None  # a trial override can disable the cache
        own_dir = None
        if spill_dir is None and est.getOrDefault("cacheDecoded"):
            import tempfile
            own_dir = spill_dir = tempfile.mkdtemp(
                prefix="sparkdl_tpu_decoded_")
        try:
            return self._trainStreamingImpl(dataset, est, spill_dir,
                                            checkpoint_tag)
        finally:
            if own_dir is not None:
                import shutil
                shutil.rmtree(own_dir, ignore_errors=True)

    def _trainStreamingImpl(self, dataset, est, spill_dir: Optional[str],
                            checkpoint_tag: str) -> KerasImageFileModel:
        """Train one configuration by streaming decoded partitions
        through the engine — no driver-memory materialization of the
        image tensor (the reference's hard boundary, SURVEY §3.4: the
        dataset had to fit in driver memory AND was broadcast whole).
        Epochs re-decode; engine host threads pipeline decode ahead of
        the device step.

        Multi-host (``jax.process_count() > 1`` after
        ``parallel.initialize``): each host streams only ITS round-robin
        partition shard, local sub-batches assemble into one global
        array over the pod-wide mesh, and XLA's gradient all-reduce
        crosses hosts — every host takes the same (globally derived)
        number of steps per epoch, so collectives stay aligned.
        ``checkpointDir`` works multi-host too: it must name a path all
        hosts can reach (GCS/NFS — the standard pod setup); orbax saves
        per epoch with every host participating, and a resumed run
        first AGREES on the restore step across hosts over DCN.
        """
        import jax

        est._validateParams()
        fit_params = est.getKerasFitParams()
        epochs = int(fit_params.get("epochs", 1))
        batch_size = int(fit_params.get("batch_size", 32))
        shuffle = bool(fit_params.get("shuffle", True))
        seed = int(fit_params.get("seed", 0))

        from sparkdl_tpu.parallel import distributed as dist
        info = dist.host_info()
        multihost = info.process_count > 1
        if multihost:
            if not est.getOrDefault("useMesh"):
                raise ValueError(
                    "multi-host streaming requires useMesh=True (the "
                    "global batch is laid out over the pod-wide mesh)")
            if dataset.num_partitions < info.process_count:
                # fail on EVERY host before any device step — a
                # mid-epoch failure on one host would leave the others
                # blocked in their first cross-host collective
                raise ValueError(
                    f"dataset has {dataset.num_partitions} partitions "
                    f"for {info.process_count} hosts; repartition with "
                    "numPartitions >= process_count so every host owns "
                    "data")

        in_col, label_col = est.getInputCol(), est.getLabelCol()
        base = dataset.select(in_col, label_col)
        loaded = est.loadImagesInternal(base, in_col, _LOADED_COL)
        loaded_local = (dist.host_shard_dataframe(loaded) if multihost
                        else loaded)
        if spill_dir is not None:
            # epoch 1 decodes and spills THIS host's shard to Arrow
            # files; later epochs stream the cache — decode runs once
            # per fit, not once per epoch (VERDICT r2 weak #5). Dir
            # lifetime is owned by _trainStreaming / fitMultiple.
            loaded_local = loaded_local.cache_to_disk(spill_dir)

        # cheap manifest (strings + labels): sizing + fingerprint —
        # identical on every host, so step counts agree everywhere.
        # Collected per partition so shard EMPTINESS is checkable:
        # partition COUNT >= host count does not guarantee every host
        # owns rows (empty partitions, filters), and a host whose shard
        # is empty would raise alone mid-epoch, hanging the others in
        # the first cross-host collective.
        import pyarrow as pa
        part_batches = list(base.stream())
        meta = pa.Table.from_batches(part_batches, schema=base.schema)
        uris = meta.column(0).to_pylist()
        labels_all = meta.column(1).to_pylist()
        n = len(uris)
        if n == 0:
            raise ValueError("cannot fit on an empty dataset")
        shard_rows: List[int] = []
        if multihost:
            counts = [b.num_rows for b in part_batches]
            for host in range(info.process_count):
                owned = dist.host_shard_indices(
                    len(counts), host, info.process_count)
                shard_rows.append(sum(counts[i] for i in owned))
                if shard_rows[-1] == 0:
                    # same computation on every host → every host
                    # raises here, before any device step
                    raise ValueError(
                        f"host {host}'s partition shard holds 0 rows "
                        f"(partition sizes {counts}); repartition so "
                        "every host owns data")

        model, loss_fn, tx, trainable, non_trainable, opt_state = \
            est._setup_trial()
        n_out = int(model.outputs[0].shape[-1])
        step = _make_step(model, loss_fn, tx)
        jitted, batch_size, mesh = est._compile_step(step, batch_size)
        # collective program (gradient all-reduce): concurrent trials in
        # THIS process must launch it in one global order
        # (parallel/mesh.py::collective_launch); across processes
        # fitMultiple already serializes trials
        launch = collective_launch(mesh)

        if multihost:
            from sparkdl_tpu.parallel.mesh import data_sharding, replicated
            # the exact mesh _compile_step jitted against — placed
            # arrays and the jit's in_shardings cannot diverge
            rep, dat = replicated(mesh), data_sharding(mesh)
            # every host holds identical (fresh or restored) values;
            # place them as replicated global arrays so the jitted
            # shardings match
            trainable, non_trainable, opt_state = jax.device_put(
                (trainable, non_trainable, opt_state), rep)
            rows_per_step = (batch_size * info.local_device_count
                             // info.global_device_count)
            if rows_per_step * info.process_count != batch_size:
                # _compile_step rounds batch_size to the data-axis size,
                # which makes this exact for every standard mesh; a
                # layout where it isn't must fail loudly on EVERY host
                # before the first collective, not deep inside sharding
                raise ValueError(
                    f"global batch {batch_size} does not split evenly "
                    f"across {info.process_count} hosts x "
                    f"{info.local_device_count} local devices "
                    f"({info.global_device_count} global); choose a "
                    "batch_size divisible by the global device count")
            # per-epoch quota sized by the LARGEST shard, not the global
            # mean: with uneven shards, ceil(n / batch) would let the
            # bigger host stop before its tail every epoch — with
            # shuffle=False the same rows would NEVER train. Sizing by
            # max(shard_rows) covers every host's full shard each epoch
            # (smaller hosts cycle, as they already do); identical on
            # every host, so collectives stay aligned.
            steps_per_epoch = max(
                1, -(-max(shard_rows) // rows_per_step))

            def place(xb, yb):
                gx = jax.make_array_from_process_local_data(
                    dat, xb, (batch_size,) + xb.shape[1:])
                gy = jax.make_array_from_process_local_data(
                    dat, yb, (batch_size,) + yb.shape[1:])
                return gx, gy
        else:
            import jax.numpy as jnp
            rows_per_step = batch_size
            steps_per_epoch = None  # derived from the stream

            def place(xb, yb):
                return jnp.asarray(xb), jnp.asarray(yb)

        # Checkpointing runs AFTER placement so the restore template in
        # a multi-host run holds the globally-replicated arrays — orbax
        # then follows its own multiprocess protocol: every host calls
        # save/restore on the SAME directory (checkpointDir must be a
        # path all hosts see — GCS/NFS in production; a per-host local
        # path deadlocks orbax's cross-host barriers, verified), the
        # primary writes, everyone restores into the global sharding.
        rng = np.random.default_rng(seed)
        history: List[float] = []
        checkpointer = None
        start_epoch = 0
        if est.isDefined("checkpointDir"):
            import os as _os

            from sparkdl_tpu.parallel.checkpoint import PytreeCheckpointer
            trial_dir = _os.path.join(
                est.getOrDefault("checkpointDir"),
                f"{checkpoint_tag}_"
                f"{self._streaming_fingerprint(est, uris, labels_all)}")
            checkpointer = PytreeCheckpointer(trial_dir)
            usable = [s for s in checkpointer.all_steps() if s <= epochs]
            local_best = max(usable) if usable else 0
            # hosts must restore the SAME step: filesystem listing
            # races would otherwise fork the replicated state and
            # deadlock the first collective
            start_epoch = (dist.agree_resume_step(local_best, usable)
                           if multihost else local_best)
            if start_epoch:
                template = {"trainable": trainable,
                            "non_trainable": non_trainable,
                            "opt_state": opt_state,
                            "history": np.zeros(start_epoch, np.float64)}
                restored = checkpointer.restore(template, step=start_epoch)
                trainable = restored["trainable"]
                non_trainable = restored["non_trainable"]
                opt_state = restored["opt_state"]
                history = [float(h) for h in restored["history"]]

        # one seed drawn per epoch (skipped epochs burn theirs, so a
        # resumed run repeats the uninterrupted run's batch order)
        epoch_seeds = [int(s) for s in
                       rng.integers(0, 2**63 - 1, size=epochs)]

        for epoch in range(start_epoch, epochs):
            with span("epoch", lane="estimator", epoch=epoch,
                      streaming=True):
                losses = []
                for xb, yb in self._epoch_stream(
                        loaded_local, label_col, rows_per_step, n_out,
                        est.getKerasLoss(), epoch_seeds[epoch], shuffle,
                        num_steps=steps_per_epoch):
                    gx, gy = place(xb, yb)
                    with span("step", lane="estimator",
                              rows=rows_per_step), \
                            watchdog_watch("estimator.step"), launch:
                        trainable, non_trainable, opt_state, loss = \
                            jitted(trainable, non_trainable, opt_state,
                                   gx, gy)
                    losses.append(loss)
                # sparkdl-lint: allow[H1] -- epoch-boundary drain: the
                # epoch's async step chain must land before loss
                # history
                history.append(float(np.mean(jax.device_get(losses))))
            if checkpointer is not None:
                # live arrays, not device_get copies: jax arrays are
                # immutable and the step donates nothing (never the
                # state, see _compile_step), so the async save reads
                # them safely — and multi-host orbax
                # needs the global arrays to run its every-host-
                # participates write protocol (a host-local numpy copy
                # would not carry the global sharding)
                checkpointer.save(
                    len(history),
                    {"trainable": trainable,
                     "non_trainable": non_trainable,
                     "opt_state": opt_state,
                     "history": np.asarray(history, np.float64)})
        if checkpointer is not None:
            checkpointer.close()

        trained = {
            # sparkdl-lint: allow[H1] -- end-of-fit drain: the trained
            # weights leave the device exactly once, here
            "trainable": jax.device_get(trainable),  # sparkdl-lint: allow[H1] -- end-of-fit drain
            "non_trainable": jax.device_get(non_trainable),  # sparkdl-lint: allow[H1] -- end-of-fit drain
        }
        mf = self._as_model_function(model, trained)
        return KerasImageFileModel(
            mf, inputCol=est.getInputCol(), outputCol=est.getOutputCol(),
            imageLoader=est.getImageLoader(), outputMode=est.getOutputMode(),
            batchSize=est.getBatchSize(),
            useMesh=est.getOrDefault("useMesh"), history=history,
            resumedFrom=start_epoch)

    # -- Estimator interface -------------------------------------------------

    def _fit(self, dataset) -> KerasImageFileModel:
        if self.getOrDefault("streaming"):
            return self._trainStreaming(dataset, {})
        X, y = self._getNumpyFeaturesAndLabels(dataset)
        return self._trainOne(X, y, {})

    # params whose override changes the localized (X, y), not just the
    # training configuration
    _DATA_PARAMS = frozenset({"inputCol", "labelCol", "imageLoader"})

    def _trialData(self, dataset, paramMap: dict, shared):
        """The (X, y) for one trial: the shared localization unless the
        paramMap overrides a data param, in which case the trial
        re-localizes with its own columns/loader."""
        names = {p.name if isinstance(p, Param) else str(p)
                 for p in paramMap}
        if names & self._DATA_PARAMS:
            return self.copy(paramMap)._getNumpyFeaturesAndLabels(dataset)
        return shared

    def fitMultiple(self, dataset, paramMaps: Sequence[dict]):
        """Yield ``(index, model)`` as trials finish — data localized
        once (the reference's broadcast) unless a trial overrides a data
        param, trials dispatched concurrently (the reference's
        one-Spark-task-per-ParamMap). With ``streaming`` nothing is
        localized; each trial streams partitions through the (shared,
        thread-safe) engine, with the same ``parallelism`` bound."""
        streaming = self.getOrDefault("streaming")
        shared = (None if streaming
                  else self._getNumpyFeaturesAndLabels(dataset))
        parallelism = max(1, self.getOrDefault("parallelism"))
        if streaming:
            import jax
            if jax.process_count() > 1 and parallelism > 1:
                # multi-controller JAX requires every process to launch
                # global computations in the SAME order — racing trial
                # threads would interleave differently per host and
                # deadlock the cross-host collectives
                import logging
                logging.getLogger(__name__).warning(
                    "multi-host streaming fitMultiple: running trials "
                    "serially (parallelism=%d ignored) to keep global "
                    "computation launch order identical on every host",
                    parallelism)
                parallelism = 1

        # one decoded-spill cache SHARED by every trial that keeps the
        # data params — the cache depends only on (inputCol, labelCol,
        # imageLoader), so per-trial caches would re-decode the dataset
        # k times, exactly the cost cacheDecoded exists to remove.
        # Concurrent trials spilling the same partition are safe:
        # unique tmp + atomic rename, deterministic decode.
        def _keeps_data_params(pm) -> bool:
            names = {p.name if isinstance(p, Param) else str(p)
                     for p in pm}
            return not (names & self._DATA_PARAMS)

        shared_spill = None
        if streaming and self.getOrDefault("cacheDecoded") \
                and any(_keeps_data_params(pm) for pm in paramMaps):
            import tempfile
            shared_spill = tempfile.mkdtemp(
                prefix="sparkdl_tpu_decoded_shared_")

        def trial(i, pm):
            if streaming:
                use_shared = (shared_spill if _keeps_data_params(pm)
                              else None)
                return self._trainStreaming(dataset, pm,
                                            checkpoint_tag=f"trial_{i}",
                                            spill_dir=use_shared)
            X, y = self._trialData(dataset, pm, shared)
            return self._trainOne(X, y, pm, checkpoint_tag=f"trial_{i}")

        try:
            if parallelism == 1 or len(paramMaps) <= 1:
                for i, pm in enumerate(paramMaps):
                    yield i, trial(i, pm)
                return

            with ThreadPoolExecutor(
                    max_workers=parallelism,
                    thread_name_prefix="sparkdl-tpu-trial") as ex:
                futs = {ex.submit(trial, i, pm): i
                        for i, pm in enumerate(paramMaps)}
                from concurrent.futures import as_completed
                for fut in as_completed(futs):
                    yield futs[fut], fut.result()
        finally:
            if shared_spill is not None:
                import shutil
                shutil.rmtree(shared_spill, ignore_errors=True)
