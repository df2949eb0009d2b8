"""TensorTransformer: apply a model to numeric/tensor columns.

Re-design of the reference's ``transformers/tf_tensor.py::TFTransformer``
(params ``tfInputGraph``/``inputMapping``/``outputMapping``): maps named
DataFrame columns onto the ModelFunction's named inputs, runs it in
device batches (or host batches for ingested TF SavedModels), and maps
named outputs back to columns.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from sparkdl_tpu.data.frame import column_index
from sparkdl_tpu.data.tensors import append_tensor_column, arrow_to_tensor
from sparkdl_tpu.obs import span
from sparkdl_tpu.params import (
    HasBatchSize,
    HasInputMapping,
    HasModelFunction,
    HasOutputMapping,
    HasTFHParams,
    HasUseMesh,
    Transformer,
    keyword_only,
)
from sparkdl_tpu.runtime.runner import RunnerMetrics


class TensorTransformer(Transformer, HasModelFunction, HasInputMapping,
                        HasOutputMapping, HasBatchSize, HasUseMesh,
                        HasTFHParams):
    @keyword_only
    def __init__(self, *, modelFunction=None, inputMapping=None,
                 outputMapping=None, batchSize=64, useMesh=False,
                 tfHParams=None):
        super().__init__()
        self._setDefault(batchSize=64, useMesh=False)
        self._set(modelFunction=modelFunction, inputMapping=inputMapping,
                  outputMapping=outputMapping, batchSize=batchSize,
                  useMesh=useMesh, tfHParams=tfHParams)
        self.metrics = RunnerMetrics()

    def _validate(self):
        mf = self.getModelFunction()
        in_map = self.getInputMapping()     # col -> input name
        out_map = self.getOutputMapping()   # output name -> col
        hparams = self.getTFHParams()       # input name -> constant
        missing = set(in_map.values()) - set(mf.input_names)
        if missing:
            raise ValueError(
                f"inputMapping references unknown model inputs {missing}; "
                f"model has {mf.input_names}")
        unknown_hp = set(hparams) - set(mf.input_names)
        if unknown_hp:
            raise ValueError(
                f"tfHParams references unknown model inputs {unknown_hp}; "
                f"model has {mf.input_names}")
        overlap = set(hparams) & set(in_map.values())
        if overlap:
            raise ValueError(
                f"model inputs {overlap} supplied by BOTH inputMapping "
                "and tfHParams")
        for name, value in hparams.items():
            shape, dtype = mf.input_signature[name]
            if shape is None or any(d is None for d in shape):
                continue  # dynamic per-row shape: nothing to check
            got = np.asarray(value, dtype=dtype).shape
            if got != tuple(shape):
                # front-load the error with names; a mismatched
                # broadcast otherwise dies mid-transform as an opaque
                # XLA arity/shape error naming neither
                raise ValueError(
                    f"tfHParams[{name!r}] has shape {got}, model input "
                    f"{name!r} expects per-row shape {tuple(shape)}")
        unmapped = set(mf.input_names) - set(in_map.values()) - set(hparams)
        if unmapped:
            raise ValueError(f"model inputs {unmapped} not mapped")
        unknown_out = set(out_map) - set(mf.output_names)
        if unknown_out:
            raise ValueError(
                f"outputMapping references unknown model outputs "
                f"{unknown_out}; model has {mf.output_names}")
        return mf, in_map, out_map, hparams

    def _transform(self, dataset):
        # once a pass: validation, the runner (with its staging and
        # ring) and the plan stage are built anew by every transform()
        with span("transform.plan", lane="engine"):
            return self._plan(dataset)

    def _plan(self, dataset):
        mf, in_map, out_map, hparams = self._validate()
        from sparkdl_tpu.transformers.utils import make_runner, reshapeRows
        runner = make_runner(mf, self.getBatchSize(),
                             use_mesh=self.getUseMesh(),
                             metrics=self.metrics)
        sig = mf.input_signature
        # once a plan, not once a call: the runner knows an announced
        # block by the memory its tensors view
        # (runtime/runner.py::inputs_identity)
        consts = {name: np.asarray(value, dtype=sig[name][1])
                  for name, value in hparams.items()}

        def to_tensors(batch: pa.RecordBatch) -> dict:
            inputs = {}
            for col, input_name in in_map.items():
                idx = column_index(batch, col)
                arr = arrow_to_tensor(batch.column(idx),
                                      batch.schema.field(idx))
                shape, dtype = sig[input_name]
                # shared seam guard (transformers.utils.reshapeRows):
                # a bare reshape error here reads as numpy noise; the
                # actual mistake is a frame whose payload doesn't
                # match the model — most often a reader
                # size/packedFormat that disagrees with
                # deviceResizeModel's
                inputs[input_name] = reshapeRows(
                    arr, shape, dtype,
                    lambda row_shape, got, expect, col=col,
                    input_name=input_name, shape=shape: (
                        f"column {col!r} rows carry {got} elements "
                        f"(row shape {row_shape}) but model input "
                        f"{input_name!r} expects shape {tuple(shape)} "
                        f"({expect} elements). The frame's payload "
                        "does not match this ModelFunction — check "
                        "the reader's size/packedFormat against the "
                        "model's (deviceResizeModel and "
                        "readImagesPacked must agree on both)"))
            for input_name, const in consts.items():
                # a hyperparameter constant rides along as a
                # row-broadcast input so the jitted program stays a
                # single fixed-arity function
                inputs[input_name] = np.broadcast_to(
                    const, (batch.num_rows,) + const.shape)
            return inputs

        # (block, its tensors) as made for the runner's look ahead:
        # kept until that block is run, so a column that had to be
        # copied to the model's dtype is still the announced memory
        announced = [None]

        def apply(batch: pa.RecordBatch, upcoming=None) -> pa.RecordBatch:
            # the hand-off between two runner.run spans, split where
            # the work happens (both children of the engine's stage:)
            with span("transform.to_tensors", lane="engine",
                      rows=batch.num_rows):
                held = announced[0]
                if held is not None and held[0] is batch:
                    inputs, announced[0] = held[1], None
                else:
                    inputs = to_tensors(batch)

            def ahead():
                # the engine's look at the block after this one (None:
                # not loaded yet), as the runner will be handed it
                nxt = upcoming()
                if nxt is None:
                    return None
                announced[0] = (nxt, to_tensors(nxt))
                return announced[0][1]

            outputs = runner.run(
                inputs, upcoming=ahead if upcoming is not None else None)
            with span("transform.append_columns", lane="engine",
                      rows=batch.num_rows):
                for output_name, col in out_map.items():
                    out = np.asarray(outputs[output_name])
                    batch = append_tensor_column(batch, col, out)
            return batch

        def close():
            # the stream ended or was abandoned: nothing announced
            # stays held, on the host or in flight
            announced[0] = None
            runner.drop_carry()

        kind = "device" if mf.backend == "jax" else "host"
        # the hint FOLLOWS the runner (LiveBatchHint) instead of
        # freezing preferred_chunk at plan build: the autotune
        # controller may move the device batch along its pre-warmed
        # shape ladder mid-stream and the engine's re-chunk cut
        # follows (data/engine.py::_stream_rechunk re-reads per block)
        from sparkdl_tpu.data.frame import LiveBatchHint
        return dataset.map_batches(
            apply, kind=kind, name=f"apply({mf.name})",
            batch_hint=(LiveBatchHint(runner) if kind == "device"
                        else None),
            with_upcoming=kind == "device",
            on_close=close if kind == "device" else None)
