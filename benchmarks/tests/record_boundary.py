"""Records the pair that ``test_boundary.py`` reads: a device trace and the program's
own spans of the same window, on one clock.

Run on the chip: ``chiprun -- python3 benchmarks/tests/record_boundary.py``.
It drives the program's real ``TensorTransformer`` over the zoo's TestNet in two
passes of three partitions of four device batches, under
``sparkdl_tpu.utils.profiling.trace``: the profiler's host tracer off, the program's
span tracer and compile log armed. It writes ``chiprun_out/trace_boundary.xplane.pb``
and ``chiprun_out/trace_boundary.program_spans.json`` (the window, the spans as
``boundary.spans_on_trace_clock`` makes them, the compiled programs' scope maps);
both are kept in ``data/``. It prints what ``boundary`` reduces them to."""

import glob
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np

BATCH, STEPS, PARTITIONS, PASSES = 1024, 4, 3, 2


def main() -> int:
    import jax
    from benchmarks import boundary, tracing
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.data.tensors import append_tensor_column
    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.obs import compile_log, tracer
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer
    from sparkdl_tpu.utils import profiling
    import pyarrow as pa

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))

    mf = getModelFunction("TestNet", featurize=True)
    shape = tuple(mf.input_signature["image"][0])
    rows = np.random.default_rng(26).integers(
        0, 255, size=(BATCH * STEPS * PARTITIONS,) + shape, dtype=np.uint8)
    parts = []
    for part in np.array_split(rows, PARTITIONS):
        index = pa.RecordBatch.from_arrays([pa.array(np.arange(len(part)))], names=["i"])
        parts.append(append_tensor_column(index, "image", part))
    transformer = TensorTransformer(modelFunction=mf, inputMapping={"image": "image"},
                                    outputMapping={"features": "features"}, batchSize=BATCH)

    def one_pass() -> int:
        stream = transformer.transform(DataFrame.from_batches(parts)).stream()
        return sum(batch.num_rows for batch in stream)

    # the warm pass compiles with the compile log armed, so that the program's scope
    # map is an event; the traced block arms both recorders itself
    compile_log().clear()
    compile_log().arm()
    assert one_pass() == len(rows)
    tracer().clear()
    log_dir = os.path.join(out_dir, "trace_boundary")
    shutil.rmtree(log_dir, ignore_errors=True)
    with profiling.trace(log_dir):
        for _ in range(PASSES):
            assert one_pass() == len(rows)
    compile_log().arm_from_env()

    records = tracer().spans()
    by_name = {r.name: r for r in records}
    zero = by_name["profiler.start_trace"].attrs["perf_counter"]
    window = [by_name["profiler.start_trace"].end - zero, by_name["profiler.stop_trace"].start - zero]
    kept = {"window": window,
            "spans": boundary.spans_on_trace_clock(records, zero),
            "programs": {e.module: e.scopes for e in compile_log().events() if e.scopes}}
    with open(os.path.join(out_dir, "trace_boundary.program_spans.json"), "w") as f:
        json.dump(kept, f, indent=0)
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(path, os.path.join(out_dir, "trace_boundary.xplane.pb"))
    print("trace bytes", os.path.getsize(path), "spans", len(records), "dropped", tracer().dropped)

    summary = tracing.reduce_trace(path, window, [])
    gaps = boundary.boundary_gaps(summary.devices[0].gaps, kept["spans"], window)
    print("window_s", summary.window_s, "busy_s", summary.busy_s, "gaps", len(summary.devices[0].gaps))
    for g in gaps:
        print("boundary", {k: round(v * 1e3, 4) if k != "run" else v for k, v in g.items()})
    print("parts", boundary.boundary_parts(gaps))
    program, seconds, total = boundary.instruction_seconds(path, window)
    scopes = kept["programs"].get(program) or {}
    print("program", program, "device_s", total, "instructions", len(seconds), "in map", len(scopes))
    print("named_share", boundary.named_share(seconds, scopes))
    print("blocks", boundary.device_blocks(seconds, scopes))
    print("unnamed", sorted((k for k in seconds if k not in scopes))[:20])
    print("dispatch_ms", [round(s * 1e3, 3) for s in boundary.span_lengths(kept["spans"], "dispatch", 0.0)])
    return 0


if __name__ == "__main__":
    sys.exit(main())
