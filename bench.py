"""North-star benchmark: InceptionV3 DeepImageFeaturizer throughput.

Output contract: the FULL
result — every key below — is written as a JSON file to
``SPARKDL_TPU_BENCH_RESULT`` (default ``bench_result.json``), and the
LAST stdout line is a compact (<1,200-char) headline carrying the
top-line numbers plus ``result_path`` — small enough for the driver's
2,000-char stdout tail window to always parse. ``tools/ci.sh``'s
schema gates read the result file.

The full result reports:

* ``value`` — the FULL measured pipeline, images/sec/chip: JPEG files
  on disk → fused native decode/resize/pack (4:2:0 planes) on engine
  host threads → ship → device-reconstructed featurize, ONE stream.
  This is the north-star metric's true shape (BASELINE.md: "end-to-end
  InceptionV3 featurization over a 1M-row image DataFrame" INCLUDES
  read+decode). Rounds 1–4 headlined the pre-decoded full-res
  transfer shape instead; that number continues as
  ``value_fullres_transfer`` for cross-round comparability, and the
  shape change is recorded here and in BASELINE.md.
* ``value_fullres_transfer`` — host-fed images/sec/chip through the
  production ``BatchRunner`` from PRE-DECODED uint8 299² NHWC host
  arrays (the rounds-1–4 ``value``): transfer-bound on this link, no
  decode included.
* ``device_resident_ips`` / ``device_tflops`` — the same program timed
  with device-resident input and a forced-sync readback: the chip's
  compute-side capability with host↔device transfer excluded.
* ``link_h2d_MBps`` / ``link_d2h_MBps`` — measured host↔device
  bandwidth, and ``host_fed_ceiling_ips`` — the hard upper bound the
  link imposes on ANY host-fed pipeline (bandwidth ÷ bytes/image).
* ``value_packed`` — end-to-end with the byte-shrunk payload
  (VERDICT r2 next #3): the host packs uint8 at a smaller source size
  (``packed_src_hw``) and bilinear resize to 299² runs ON DEVICE,
  fused into the same XLA program (``deviceResizeFrom`` mode) — the
  wire carries ~4× fewer bytes/image, lifting the link ceiling
  (``host_fed_ceiling_ips_packed``) in proportion.
* ``host_decode_ips`` — the fused decode→resize→pack reader
  (``readImagesPacked``, native libjpeg+OpenMP shim) measured on
  synthesized TEXTURED JPEGs (photo-like compressibility): proof the
  host decode stage outruns the device featurize rate budgeted in
  SURVEY §6.
* ``value_packed420`` / ``host_fed_ceiling_ips_packed420`` — the
  payload halved again (VERDICT r4 next #1): planar YCbCr 4:2:0 at
  1.5 B/px shipped, chroma upsample + BT.601 reconstruction + resize
  fused on-device (``packedFormat="yuv420"``).
* ``value_packed420_fullres`` — the NO-resolution-loss packed shape:
  298² 4:2:0 planes (even-dims; ~133 KB/img, half the 299² RGB
  payload) device-resized the 1px to the model's 299² — for pipelines
  that must not trade source resolution for link bytes.
* ``value_pipeline`` — same number as ``value`` (kept under the round
  2–4 key so round-over-round tooling reads continuously);
  ``pipeline_bound_by`` names the stage (decode | link | compute)
  whose own measured ceiling binds it.
* ``serve`` — the online-serving shape (docs/SERVING.md): concurrent
  sub-batch requests through the ModelServer's dynamic micro-batching
  front-end — offered vs achieved rows/sec, mean batch fill ratio,
  p99 request latency, rejection/deadline-miss/failure counts.
  tools/ci.sh gates the schema and (armed) the fill ratio +
  serve-lane trace.
* ``tails`` — per-request tail attribution (docs/OBSERVABILITY.md):
  the serve pass runs with the request log armed, and the measured
  request p50/p99 plus the p99 specimen's phase breakdown
  (queue/coalesce/staging/device/reassembly) come from the recorded
  timelines. tools/ci.sh gates the schema and the ≥95% attribution
  bar.
* ``bound`` — the live roofline (sparkdl_tpu/obs/ledger.py,
  docs/PERFORMANCE.md): one utilization-ledger window over the
  measured pipeline pass — per-stage utilization fractions
  (decode/link/compute/serve), the continuous ``bound_by`` verdict
  with its headroom, the probed/injected ceilings, and the offline
  ceilings-based twin. ``pipeline_bound_by`` itself is re-derived
  through the SAME ``ledger.attribute()`` call, so the offline and
  live verdicts are one code path. tools/ci.sh gates the schema,
  the [0,1] bounds, and verdict == max-utilization stage.
* ``compile`` — compile forensics (docs/OBSERVABILITY.md,
  obs/compile_log.py): the run's jit compiles per function with wall
  time, cost/memory analysis, retrace attribution (a diff naming the
  argument that moved), and the steady-state zero-retrace verdict
  (``unexpected_retraces`` — the warmed serve pass must report 0);
  ``device_gflops_ceiling`` is the model-calibrated compute roofline
  the ledger's ``compute_basis`` divides by. tools/ci.sh gates the
  schema, the clean-pass zero, and an injected off-ladder shape
  showing the attributed retrace.
* ``autotune`` — the closed-loop infeed autotuner
  (sparkdl_tpu/autotune, docs/PERFORMANCE.md): tuned-vs-fixed
  throughput with the baseline's recorded noise band, decision /
  oscillation / clamp counts, and the converged knob config.
  tools/ci.sh gates schema + convergence (settled, zero
  oscillations, no loss outside the band).

Separating these is the point: a host-fed pipeline can sit far below
what the device program itself sustains. ``vs_baseline`` stays honest
(end-to-end vs the 1,250 img/s/chip target = 10k/s ÷ 8 chips,
BASELINE.md) and the extra keys attribute any gap to link vs compute.

Sync methodology: dispatch is asynchronous, so every timed region ends
in a tiny dependent readback (utils/measure.py).

The backend is whatever JAX selects; the bench never decides by itself
to run on the CPU. ``JAX_PLATFORMS=cpu`` is the explicit way to do so
(tools/ci.sh's tiny smoke).

The ``"obs"`` block carries the unified observability layer's output
(docs/OBSERVABILITY.md): the metrics-registry snapshot always, plus
the exported Perfetto trace path/span count when ``SPARKDL_TPU_TRACE=1``
armed the run (``SPARKDL_TPU_TRACE_EXPORT`` names the path).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

PER_CHIP_TARGET = 1250.0  # 10k img/s ÷ 8 chips (BASELINE.md)
INCEPTION_GFLOPS = 11.5   # fwd FLOPs per 299x299 image (SURVEY §6)

# SPARKDL_TPU_BENCH_TINY=1: the CI smoke shape — TestNet instead of
# InceptionV3, tiny corpora, same JSON contract. tools/ci.sh runs this
# under JAX_PLATFORMS=cpu and gates on the emitted schema (every key a
# round-over-round reader or the driver contract consumes must be
# present), so a bench refactor that drops pipeline_bound_by, a
# ceiling, or the host-copy counters fails CI instead of failing the
# next TPU round.
BENCH_TINY = os.environ.get("SPARKDL_TPU_BENCH_TINY") == "1"


def measure_host_decode(size=(299, 299), n_images: int = 64,
                        packedFormat: str = "rgb") -> float:
    """images/sec through the fused decode→resize→pack reader on a
    TEXTURED corpus (photo-like ~2 bits/pixel; round-3's noise JPEGs
    sat at ~7 bpp and understated throughput ~3× — VERDICT r3 weak #8),
    best of 2 passes (pass 1 warms the page cache, builds the shim)."""
    import shutil
    import tempfile

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.utils.synth import write_textured_jpegs

    d = tempfile.mkdtemp(prefix="sparkdl_bench_decode_")
    try:
        write_textured_jpegs(d, n_images)
        df = imageIO.readImagesPacked(d, size, numPartitions=4,
                                      packedFormat=packedFormat)
        rates = []
        for _ in range(2):
            t0 = time.perf_counter()
            table = df.collect()
            rates.append(table.num_rows / (time.perf_counter() - t0))
        return float(max(rates))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def measure_pipeline(mf, packed_src, batch_size: int,
                     n_images: int, packedFormat: str = "rgb") -> dict:
    """THE full-pipeline headline (VERDICT r3 next #1): JPEG files on
    disk → ``readImagesPacked(packed_src)`` (fused native
    decode→resize→pack on engine host threads) → device-resized
    featurize — ONE streamed pipeline, decode running ahead of device
    dispatch (host stages parallelize across partitions while the
    device stage serializes under the device lock). images/sec over the
    whole corpus, single pass per repeat, best of 2 (pass 1 is
    steady-state warmup for the jit + page cache). Returns the rate
    plus the runner's host-copy counters over both passes — the proof
    the ship path stages/copies what it claims and nothing more."""
    import shutil
    import tempfile

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer
    from sparkdl_tpu.transformers.utils import deviceResizeModel, single_io
    from sparkdl_tpu.utils.synth import write_textured_jpegs

    d = tempfile.mkdtemp(prefix="sparkdl_bench_pipe_")
    try:
        write_textured_jpegs(d, n_images)
        mf_packed = deviceResizeModel(mf, packed_src,
                                      packedFormat=packedFormat)
        in_name, out_name = single_io(mf_packed)
        t = TensorTransformer(modelFunction=mf_packed,
                              inputMapping={"image": in_name},
                              outputMapping={out_name: "features"},
                              batchSize=batch_size)
        # partition count is deliberately batch-MISALIGNED: the engine's
        # cross-partition re-chunking (Stage.batch_hint) feeds the
        # device stage batch-aligned blocks regardless, so the 2.4×
        # small-partition padding tax of rounds ≤4 no longer applies
        # (r4 measured 130 img/s at 32-row partitions vs ~310 aligned;
        # the old workaround sized partitions to the batch)
        parts = 8
        rates = []
        for _ in range(2):
            df = imageIO.readImagesPacked(d, packed_src,
                                          numPartitions=parts,
                                          packedFormat=packedFormat)
            out = t.transform(df)
            n = 0
            t0 = time.perf_counter()
            for b in out.stream():
                n += b.num_rows
            elapsed = time.perf_counter() - t0
            assert n == n_images, (n, n_images)
            rates.append(n / elapsed)
        m = t.metrics
        # the measured pipeline's ship counters also land in the obs
        # registry so the bench "obs" block carries them
        from sparkdl_tpu.obs import default_registry
        m.publish(default_registry())
        return {"ips": float(max(rates)),
                "bytes_staged": int(m.bytes_staged),
                "bytes_copied": int(m.bytes_copied),
                "transfer_wait_s": round(m.transfer_wait_seconds, 4)}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def measure_pipeline_overlap(mf, packed_src, batch_size: int,
                             n_images: int,
                             packedFormat: str = "rgb") -> dict:
    """The parallel host pipeline's proof block (ROADMAP item 3,
    docs/PERFORMANCE.md "Parallel host pipeline"): the SAME
    disk→decode→ship→featurize pipeline as :func:`measure_pipeline`,
    measured twice on ONE corpus — once through the serial engine
    (``pipeline_workers=0``) and once through the pooled engine
    (``SPARKDL_TPU_PIPELINE_WORKERS`` or 2) — plus the overlap proof:
    ``overlap_ratio = (decode_busy + ship_busy) / wall`` over the
    pooled pass's best timed run. Ratio > 1 is only possible when
    decode genuinely overlaps ship/dispatch; on a 1-core host the
    pooled path auto-degrades to serial (``mode: "serial"``) and the
    ratio honestly stays ≤ ~1. tools/ci.sh's pipeline gate reads this
    block."""
    import shutil
    import tempfile

    from sparkdl_tpu.data import pipeline as host_pipeline
    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.obs import default_registry
    from sparkdl_tpu.transformers.tensor_transform import TensorTransformer
    from sparkdl_tpu.transformers.utils import deviceResizeModel, single_io

    from sparkdl_tpu.utils.synth import write_textured_jpegs

    d = tempfile.mkdtemp(prefix="sparkdl_bench_overlap_")
    try:
        write_textured_jpegs(d, n_images)
        mf_packed = deviceResizeModel(mf, packed_src,
                                      packedFormat=packedFormat)
        in_name, out_name = single_io(mf_packed)
        reg = default_registry()

        def one_pass(engine):
            # best of 2 (pass 1 is jit/page-cache warmup), with the
            # best pass's busy/wall accounting for the overlap ratio
            best = None
            for _ in range(2):
                df = imageIO.readImagesPacked(
                    d, packed_src, numPartitions=8,
                    packedFormat=packedFormat, engine=engine)
                t = TensorTransformer(modelFunction=mf_packed,
                                      inputMapping={"image": in_name},
                                      outputMapping={out_name: "features"},
                                      batchSize=batch_size)
                out = t.transform(df)
                decode0 = reg.counter("engine.busy_seconds").value
                ship0 = reg.counter("device.run_seconds").value
                n = 0
                t0 = time.perf_counter()
                for b in out.stream():
                    n += b.num_rows
                wall = time.perf_counter() - t0
                assert n == n_images, (n, n_images)
                row = {
                    "ips": n / wall, "wall_s": wall,
                    "decode_busy_s":
                        reg.counter("engine.busy_seconds").value
                        - decode0,
                    "ship_busy_s":
                        reg.counter("device.run_seconds").value
                        - ship0,
                }
                if best is None or row["ips"] > best["ips"]:
                    best = row
            return best

        requested = host_pipeline.resolve_workers(None) or 2
        serial_engine = LocalEngine(pipeline_workers=0)
        pooled_engine = LocalEngine(pipeline_workers=requested)
        try:
            serial = one_pass(serial_engine)
            pooled = one_pass(pooled_engine)
        finally:
            serial_engine.shutdown()
            pooled_engine.shutdown()
        effective = host_pipeline.effective_workers(
            requested, pooled_engine.pipeline_mode, record=False)
        mode = (host_pipeline.state().get("mode") or "serial") \
            if effective >= 2 else "serial"
        ratio = (pooled["decode_busy_s"] + pooled["ship_busy_s"]) \
            / max(pooled["wall_s"], 1e-9)
        return {
            "workers": requested,
            "effective_workers": effective,
            "read_ahead": int(pooled_engine.pipeline_read_ahead),
            "mode": mode,
            "serial_ips": round(serial["ips"], 1),
            "pooled_ips": round(pooled["ips"], 1),
            "pooled_vs_serial": round(
                pooled["ips"] / max(serial["ips"], 1e-9), 3),
            "overlap_ratio": round(ratio, 3),
            "decode_busy_s": round(pooled["decode_busy_s"], 4),
            "ship_busy_s": round(pooled["ship_busy_s"], 4),
            "wall_s": round(pooled["wall_s"], 4),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def measure_fidelity(mf, packed_src, n_images: int = 32) -> dict:
    """Quantify what the packed-ship headline shape costs in feature
    fidelity (VERDICT r4 #2): the same JPEG corpus featurized through
    (a) full decode→native-res RGB and (b) the ``packed_src`` yuv420
    ship + fused device reconstruct/resize, compared row-wise by
    cosine.

    THREE numbers, because the raw cosine alone is vacuous under this
    env's seeded-random weights: random-BN features share a large
    constant component, so DIFFERENT images already cosine ~0.998 —
    any pipeline would "score" 1.0. ``centered`` subtracts each path's
    corpus-mean feature first (the discriminative part that transfer
    learning actually consumes), and ``cross_image_centered_baseline``
    is the same metric between MISMATCHED rows — the floor the path
    cosine must clear to mean anything (measured ~0.03 vs ~0.999
    same-image). End-accuracy parity on the capstone task is pinned in
    tests/test_integration_capstone.py::test_packed_ship_fidelity."""
    import shutil
    import tempfile

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.runtime.runner import BatchRunner
    from sparkdl_tpu.transformers.utils import deviceResizeModel, single_io
    from sparkdl_tpu.utils.synth import write_textured_jpegs

    in_name, out_name = single_io(mf)
    (h, w, _c), _ = mf.input_signature[in_name]
    d = tempfile.mkdtemp(prefix="sparkdl_bench_fid_")
    try:
        write_textured_jpegs(d, n_images)
        full = imageIO.readImagesPacked(d, (h, w),
                                        numPartitions=2).tensor("image")
        packed = imageIO.readImagesPacked(
            d, packed_src, numPartitions=2,
            packedFormat="yuv420").tensor("image")
        fa = BatchRunner(mf, batch_size=n_images).run(
            {in_name: full})[out_name]
        mfp = deviceResizeModel(mf, packed_src, packedFormat="yuv420")
        fb = BatchRunner(mfp, batch_size=n_images).run(
            {in_name: packed})[out_name]
        fa = np.asarray(fa).reshape(n_images, -1)
        fb = np.asarray(fb).reshape(n_images, -1)

        def cos_rows(a, b):
            return (a * b).sum(1) / np.maximum(
                np.linalg.norm(a, axis=1) * np.linalg.norm(b, axis=1),
                1e-9)

        cos = cos_rows(fa, fb)
        ca, cb = fa - fa.mean(0), fb - fb.mean(0)
        cen = cos_rows(ca, cb)
        base = cos_rows(ca, np.roll(cb, 1, axis=0))
        return {"feature_cosine_mean": round(float(cos.mean()), 4),
                "feature_cosine_min": round(float(cos.min()), 4),
                "centered_cosine_mean": round(float(cen.mean()), 4),
                "centered_cosine_min": round(float(cen.min()), 4),
                "cross_image_centered_baseline": round(
                    float(base.mean()), 4),
                "paths": f"decode->{h}x{w} RGB vs {packed_src[0]}x"
                         f"{packed_src[1]} yuv420 ship + device resize"}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def measure_serve(mf, batch_size: int, n_requests: int,
                  rows_per_request: int, threads: int = 4) -> tuple:
    """The online-serving shape (docs/SERVING.md): a ModelServer over
    the production BatchRunner, hammered by concurrent submitter
    threads at offered load above the bounded queue's capacity.
    Reports offered vs achieved rows/sec, the mean batch fill ratio
    (what dynamic micro-batching exists to maximize), p99 request
    latency, and the rejection count — the backpressure contract made
    a number instead of an assertion. Requests are sized at a fraction
    of the device batch so the achieved rate is earned by coalescing,
    not by callers pre-batching; a couple of OVERSIZED requests ride
    along so the tails block sees the split-and-reassemble path.

    Returns ``(serve_block, tails_block)``: the request log is armed
    for the measurement window, so every request records a phase
    timeline and the ``"tails"`` block attributes the measured p99
    across phases (tails_from_records)."""
    import threading as th

    from sparkdl_tpu.obs.request_log import request_log, tails_from_records
    from sparkdl_tpu.serve import ModelServer, ServeConfig, ServerOverloaded

    in_name = mf.input_names[0]
    shape, dtype = mf.input_signature[in_name]
    server = ModelServer(ServeConfig(
        max_wait_s=0.05,
        max_queue_rows=max(batch_size * 8,
                           rows_per_request * threads * 2)))
    server.register("bench", mf, batch_size=batch_size)
    server.warmup()

    rlog = request_log()
    # save the OVERRIDE, not the derived armed bit: an env/tracer-armed
    # log must come back override-free (a stuck override would outlive
    # the tracer's disarm), and a caller's explicit disarm must survive
    # this measurement (the flight.autoarm override-inspection precedent)
    rlog_override = rlog._override
    rlog.arm()
    rlog.clear()

    futures, lock = [], th.Lock()

    def fire(tid: int):
        rng = np.random.default_rng(tid)
        x = rng.integers(0, 255, (rows_per_request,) + tuple(shape)
                         ).astype(dtype)
        for _ in range(n_requests):
            try:
                f = server.submit({in_name: x})
            except ServerOverloaded:
                pass    # counted by ServeMetrics.rejections
            else:
                with lock:
                    futures.append(f)

    workers = [th.Thread(target=fire, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    # the split-path specimens: two requests larger than the device
    # batch, so the tails block covers reassembled multi-batch flows
    rng = np.random.default_rng(99)
    big = rng.integers(0, 255, (batch_size + rows_per_request,)
                       + tuple(shape)).astype(dtype)
    for _ in range(2):
        try:
            f = server.submit({in_name: big})
        except ServerOverloaded:
            pass
        else:
            with lock:
                futures.append(f)
    for w in workers:
        w.join()
    # offered load is a SUBMISSION-side rate: clocked at worker join,
    # before the result drain — folding the drain into it would pull
    # offered toward achieved and erase exactly the gap this block
    # exists to report
    submit_elapsed = max(time.perf_counter() - t0, 1e-9)
    completed_rows = 0
    for f in futures:
        out = f.result()
        completed_rows += len(next(iter(out.values())))
    elapsed = time.perf_counter() - t0
    server.close()
    tails = tails_from_records(rlog.records())
    rlog._override = rlog_override
    m = server.metrics.as_dict()
    offered_rows = (threads * n_requests * rows_per_request
                    + 2 * len(big))
    serve = {
        "offered_rows_per_s": round(offered_rows / submit_elapsed, 1),
        "achieved_rows_per_s": round(completed_rows / elapsed, 1),
        "requests": m["requests"],
        "rows": m["rows"],
        "batches": m["batches"],
        "batch_fill_ratio": m["batch_fill_ratio"],
        "p99_latency_ms": m["latency_p99_ms"],
        "rejections": m["rejections"],
        "deadline_misses": m["deadline_misses"],
        "failures": m["failures"]}
    return serve, tails


def measure_autotune(mf, batch_size: int, n_rows: int) -> dict:
    """The closed-loop infeed autotuner's acceptance shape
    (docs/PERFORMANCE.md): a RunnerTarget-tuned prefetch runner vs the
    fixed ``host_async`` expert default, same model, same rows.

    Phases: (1) baseline — 3 passes through the static host_async
    runner; the pass-to-pass spread is the recorded noise band the
    tuned number is judged inside (a single-point comparison says
    nothing without the run-to-run spread). (2) settle — the armed controller steps on
    every pass (interval 0) while the tuned runner runs its warmup +
    settle window; trials/reverts happen HERE. (3) converged — timed
    passes with the decision counter snapshotted around them:
    ``changes_after_warmup`` and ``oscillations`` are what tools/ci.sh
    gates (a controller that keeps hunting after its settle window is
    worse than no controller)."""
    from sparkdl_tpu.autotune import RunnerTarget, controller
    from sparkdl_tpu.obs import default_registry
    from sparkdl_tpu.runtime.runner import BatchRunner

    in_name = mf.input_names[0]
    shape, dtype = mf.input_signature[in_name]
    rng = np.random.default_rng(7)
    x = rng.integers(0, 255, (n_rows,) + tuple(shape)).astype(dtype)
    warm = {in_name: x[:batch_size]}
    full = {in_name: x}

    def passes(runner, n):
        rates = []
        for _ in range(n):
            t0 = time.perf_counter()
            runner.run(full)
            rates.append(n_rows / (time.perf_counter() - t0))
        return rates

    baseline = BatchRunner(mf, batch_size=batch_size,
                           strategy="host_async")
    baseline.run(warm)                      # compile warmup
    base_rates = passes(baseline, 3)
    baseline_ips = float(max(base_rates))
    noise_band = (max(base_rates) - min(base_rates)) / max(base_rates)

    ctl = controller()
    reg = default_registry()
    # the tuned runner starts from the default strategy (the config a
    # user who set nothing gets): the
    # controller's job is to beat-or-match the default it inherits,
    # not a hand-picked shape. prefetch-depth tuning is pinned in
    # tests/test_autotune.py and measured by measure_transfer --sweep.
    tuned = BatchRunner(mf, batch_size=batch_size)
    try:
        ctl.attach(RunnerTarget(tuned))
        ctl.arm(interval_s=0.0)             # step on every pass
        tuned.run(warm)                     # compile warmup
        # settle window: long enough for BOTH overlap knobs to run a
        # full explore→evaluate(→revert+freeze) trial before the timed
        # passes — the convergence gate counts changes AFTER this
        passes(tuned, 6)
        decisions_before = reg.counter("autotune.decisions").value
        tuned_rates = passes(tuned, 3)
        changes_after = (reg.counter("autotune.decisions").value
                         - decisions_before)
        state = ctl.state()
    finally:
        ctl.reset()                         # detach + follow the env
    return {
        "armed": True,
        "strategy": tuned.strategy,
        "baseline_strategy": baseline.strategy,
        "baseline_ips": round(baseline_ips, 1),
        "tuned_ips": round(float(max(tuned_rates)), 1),
        "noise_band_pct": round(noise_band * 100.0, 1),
        "decisions": int(state["decisions"]),
        "changes_after_warmup": int(changes_after),
        "oscillations": int(state["oscillations"]),
        "clamps": int(state["clamps"]),
        "steps": int(state["steps"]),
        "converged": {
            "max_inflight": int(tuned.max_inflight),
            "prefetch_depth": int(tuned.prefetch_depth),
        },
    }


def measure_ship_ring(mf, batch_size: int, n_rows: int) -> dict:
    """The device-resident infeed ring's acceptance shape
    (docs/PERFORMANCE.md "Infeed ring & transfer interleave"): a
    repeated-corpus steady pass through a ringed prefetch runner vs
    the same runner with no ring, same model, same rows. The ring is
    sized to hold the whole corpus (depth = corpus chunks, floored at
    2) — the shape serving steady traffic actually sees, and the one
    the zero-re-ship guarantee is defined over. tools/ci.sh gates:
    ``steady_bytes_reshipped == 0``, ``steady_bytes_shipped == 0``
    (every steady byte served from resident HBM),
    ``unexpected_retraces == 0`` (the donated program compiled at
    warmup, never at a steady request), and ring_ips against the
    no-ring baseline inside the same noise discipline as
    measure_autotune."""
    from sparkdl_tpu.obs import default_registry
    from sparkdl_tpu.runtime.runner import BatchRunner, warmup_runner

    in_name = mf.input_names[0]
    shape, dtype = mf.input_signature[in_name]
    rng = np.random.default_rng(7)
    x = rng.integers(0, 255, (n_rows,) + tuple(shape)).astype(dtype)
    full = {in_name: x}
    corpus_chunks = -(-n_rows // batch_size)
    depth = max(2, corpus_chunks)
    reg = default_registry()

    def passes(runner, n):
        rates = []
        for _ in range(n):
            t0 = time.perf_counter()
            runner.run(full)
            rates.append(n_rows / (time.perf_counter() - t0))
        return rates

    baseline = BatchRunner(mf, batch_size=batch_size,
                           strategy="prefetch")
    warmup_runner(baseline)
    base_rates = passes(baseline, 3)
    baseline_ips = float(max(base_rates))
    noise_band = (max(base_rates) - min(base_rates)) / max(base_rates)
    # the no-ring pass re-ships the whole corpus every time — the
    # per-pass link traffic the ring's steady pass is gated to kill
    s0 = reg.counter("ship.bytes_shipped").value
    baseline.run(full)
    baseline_bytes = reg.counter("ship.bytes_shipped").value - s0

    ringed = BatchRunner(mf, batch_size=batch_size,
                         strategy="prefetch", infeed_ring=depth)
    warmup_runner(ringed)
    ringed.run(full)                         # fill pass (ships once)
    retr0 = reg.counter("compile.unexpected_retraces").value
    h0 = reg.counter("ship.ring_hits").value
    r0 = reg.counter("ship.bytes_reshipped").value
    s0 = reg.counter("ship.bytes_shipped").value
    res0 = reg.counter("ship.bytes_resident").value
    ring_rates = passes(ringed, 3)
    return {
        "batch": int(batch_size),
        "rows": int(n_rows),
        "ring_depth": int(ringed.infeed_ring),
        "corpus_chunks": int(corpus_chunks),
        "baseline_ips": round(baseline_ips, 1),
        "ring_ips": round(float(max(ring_rates)), 1),
        "noise_band_pct": round(noise_band * 100.0, 1),
        "baseline_bytes_per_pass": int(baseline_bytes),
        "steady_bytes_shipped": int(
            reg.counter("ship.bytes_shipped").value - s0),
        "steady_bytes_reshipped": int(
            reg.counter("ship.bytes_reshipped").value - r0),
        "steady_ring_hits": int(
            reg.counter("ship.ring_hits").value - h0),
        "steady_bytes_resident": int(
            reg.counter("ship.bytes_resident").value - res0),
        "unexpected_retraces": int(
            reg.counter("compile.unexpected_retraces").value - retr0),
        "ring_state": ringed.ring_state(),
    }


def measure_input_service(n_rows: int = 4096,
                          n_partitions: int = 8) -> dict:
    """The disaggregated input service's acceptance shape
    (docs/DATA_SERVICE.md): the SAME decode plan over ONE synthetic
    corpus run three ways — local pooled decode, a one-worker remote
    decode fleet (in-process ``DecodeServer`` over the real socket
    transport), and a two-worker fleet — plus the snapshot tier's
    epoch amortization: a cold snapshot epoch (decode + persist) vs a
    warm epoch (stream packed chunks straight off disk), with the warm
    pass's ``engine.busy_seconds`` delta as the decode-work proof.
    tools/ci.sh's input-service gate re-proves the warm-busy ≈ 0 and
    row-identity claims in a two-process drill; this block carries the
    measured rows/s so bench_compare can track regressions."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.compute as pc

    from sparkdl_tpu.data.engine import LocalEngine
    from sparkdl_tpu.data.frame import DataFrame
    from sparkdl_tpu.inputsvc import DecodeServer
    from sparkdl_tpu.obs import default_registry

    reg = default_registry()
    table = pa.table({
        "id": pa.array(range(n_rows), type=pa.int64()),
        "x": pa.array([float(i % 997) for i in range(n_rows)],
                      type=pa.float64()),
    })

    def plan(df):
        def work(batch):
            i = batch.schema.get_field_index("x")
            col = batch.column("x")
            for _ in range(8):           # give decode measurable work
                col = pc.add(pc.multiply(col, 1.0000001), 0.5)
            return batch.set_column(i, "x", col)
        return df.map_batches(work, name="bench_decode")

    def timed_collect(engine):
        df = plan(DataFrame.from_table(table, n_partitions, engine))
        t0 = time.perf_counter()
        out = df.collect()
        wall = time.perf_counter() - t0
        assert out.num_rows == n_rows, (out.num_rows, n_rows)
        return n_rows / max(wall, 1e-9)

    local_engine = LocalEngine()
    try:
        local_ips = max(timed_collect(local_engine) for _ in range(2))
    finally:
        local_engine.shutdown()

    servers = [DecodeServer().start() for _ in range(2)]
    fleet = [f"127.0.0.1:{s.port}" for s in servers]
    remote = {}
    try:
        for width in (1, 2):
            eng = LocalEngine(inputsvc_endpoints=fleet[:width])
            try:
                remote[width] = max(timed_collect(eng)
                                    for _ in range(2))
            finally:
                eng.shutdown()
    finally:
        for s in servers:
            s.close()

    snap_root = tempfile.mkdtemp(prefix="sparkdl_bench_snap_")
    snap_engine = LocalEngine()
    try:
        base = plan(DataFrame.from_table(table, n_partitions,
                                         snap_engine))

        def epoch():
            busy0 = reg.counter("engine.busy_seconds").value
            df = base.snapshot(snap_root, fingerprint="bench-corpus")
            t0 = time.perf_counter()
            out = df.collect()
            wall = time.perf_counter() - t0
            assert out.num_rows == n_rows
            busy = reg.counter("engine.busy_seconds").value - busy0
            return n_rows / max(wall, 1e-9), busy

        cold_ips, cold_busy = epoch()
        warm_ips, warm_busy = epoch()
    finally:
        snap_engine.shutdown()
        shutil.rmtree(snap_root, ignore_errors=True)

    counters = reg.snapshot()
    return {
        "rows": int(n_rows),
        "partitions": int(n_partitions),
        "local_ips": round(local_ips, 1),
        "remote_ips_1worker": round(remote[1], 1),
        "remote_ips_2workers": round(remote[2], 1),
        "remote_vs_local_1worker": round(
            remote[1] / max(local_ips, 1e-9), 3),
        "remote_vs_local_2workers": round(
            remote[2] / max(local_ips, 1e-9), 3),
        "snapshot_cold_ips": round(cold_ips, 1),
        "snapshot_warm_ips": round(warm_ips, 1),
        "snapshot_warm_vs_cold": round(
            warm_ips / max(cold_ips, 1e-9), 3),
        # the amortization proof: a warm epoch streams packed chunks,
        # it does not re-run decode — this must read ~0 while the cold
        # epoch's busy covers the whole corpus
        "cold_decode_busy_s": round(cold_busy, 4),
        "warm_decode_busy_s": round(warm_busy, 4),
        "rpc_errors": int(counters.get("inputsvc.rpc_errors", 0)),
        "local_failovers": int(
            counters.get("inputsvc.local_decodes", 0)),
        "snapshot_hits": int(
            counters.get("inputsvc.snapshot_hits", 0)),
        "snapshot_misses": int(
            counters.get("inputsvc.snapshot_misses", 0)),
    }


def measure_fleet(batch_size: int = 16) -> dict:
    """The fleet control plane's acceptance numbers (docs/SERVING.md
    "Fleet control plane"): on one small synthetic model,

    * **swap latency** — deploy at 2 replicas, hot-swap the weights
      (``ModelRegistry.swap_weights``: stage → flip → zero-retrace
      probe) and report the measured wall plus the output-flip and
      zero-``unexpected_retraces`` proofs;
    * **cold vs warm first request** — the same signature deployed
      cold (empty warm-start cache: first request pays the compile)
      and then fresh into a NEW server from the now-populated cache
      (AOT deserialize: ``compiles_of`` must read ZERO). ci.sh's
      step-22 drill re-proves this across a real process boundary;
      this block carries the measured milliseconds;
    * **packing decision** — the live planner's verdict for this
      model at 2 replicas against the measured/assumed device budgets
      (the same plan tools/fleet_pack.py prints).
    """
    import shutil
    import tempfile

    from sparkdl_tpu.fleet import ModelRegistry, WarmStartCache
    from sparkdl_tpu.fleet.placement import (estimate_footprint,
                                             plan_placement)
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.obs.compile_log import compile_log
    from sparkdl_tpu.serve import ModelServer, ServeConfig

    dim = 8

    def apply(params, inputs):
        return {"y": inputs["x"] @ params["w"]}

    def fresh_mf(name: str, scale: float) -> ModelFunction:
        params = {"w": (scale * np.eye(dim)).astype(np.float32)}
        return ModelFunction(apply, params,
                             {"x": ((dim,), np.float32)}, ["y"],
                             name=name)

    x = np.ones((batch_size, dim), np.float32)
    cache_root = tempfile.mkdtemp(prefix="sparkdl_bench_fleet_")
    clog = compile_log()
    out: dict = {}
    try:
        cache = WarmStartCache(cache_root)
        server = ModelServer(ServeConfig(max_wait_s=0.0))
        reg = ModelRegistry(server, warmstart=cache)
        try:
            # cold: empty cache, no warmup — the first request pays
            # the jit compile, and deploy persists the AOT blob
            reg.deploy("fleetcold", fresh_mf("fleetcold", 2.0),
                       batch_size=batch_size, replicas=1,
                       warmup=False)
            t0 = time.perf_counter()
            y = reg.submit({"x": x}, model="fleetcold").result()["y"]
            cold_ms = (time.perf_counter() - t0) * 1000.0
            assert float(np.asarray(y)[0, 0]) == 2.0, y[0, 0]

            # in-process scale-out: replica r1 warm-starts from the
            # blob the cold deploy just persisted
            reg.scale("fleetcold", 2)

            # the swap: same shapes, new values — flip under load
            # machinery, probe for retraces, report the wall
            retraces0 = clog.unexpected_retraces
            reg.swap_weights("fleetcold",
                             {"w": (3.0 * np.eye(dim)
                                    ).astype(np.float32)})
            y2 = reg.submit({"x": x}, model="fleetcold").result()["y"]
            st = reg.state()
            out.update({
                "swap_ms": st["last_swap_ms"],
                "swap_output_flipped":
                    float(np.asarray(y2)[0, 0]) == 3.0,
                "swap_retraces":
                    clog.unexpected_retraces - retraces0,
                "swaps": st["swaps"],
                "swap_failures": st["swap_failures"],
            })
        finally:
            server.close()

        # warm: a NEW server + registry, a fresh same-signature
        # model — first request must deserialize, not compile
        server2 = ModelServer(ServeConfig(max_wait_s=0.0))
        reg2 = ModelRegistry(server2, warmstart=cache)
        try:
            reg2.deploy("fleetwarm", fresh_mf("fleetwarm", 5.0),
                        batch_size=batch_size, replicas=1,
                        warmup=False)
            t0 = time.perf_counter()
            y3 = reg2.submit({"x": x}).result()["y"]
            warm_ms = (time.perf_counter() - t0) * 1000.0
            assert float(np.asarray(y3)[0, 0]) == 5.0, y3[0, 0]
            out.update({
                "cold_first_request_ms": round(cold_ms, 2),
                "warm_first_request_ms": round(warm_ms, 2),
                "warm_vs_cold": round(warm_ms / max(cold_ms, 1e-9),
                                      3),
                "warm_compiles":
                    clog.compiles_of("fleetwarm@r0.jitted"),
                "warmstart": cache.state(),
            })
            # the packing decision for THIS model at 2 replicas,
            # against the live (or assumed) budgets
            fp = estimate_footprint(reg2.entry("fleetwarm").model_fn,
                                    batch_size)
            plan = plan_placement([fp],
                                  replicas={fp.name: 2})
            out["placement"] = {
                "footprint_bytes": fp.bytes,
                "footprint_source": fp.detail["source"],
                "mode": plan.mode[fp.name],
                "devices": plan.assignments[fp.name],
            }
        finally:
            server2.close()
    finally:
        shutil.rmtree(cache_root, ignore_errors=True)
    return out


_bench_done = None  # set by main(); threading.Event


def _start_watchdog(seconds: int = 2400) -> None:
    """A run that stalls instead of failing would otherwise end with no
    JSON line at all. After ``seconds`` the watchdog prints a minimal
    contract line naming the overrun and exits non-zero; a finished
    main() disarms it."""
    import os
    import threading

    global _bench_done
    _bench_done = threading.Event()

    def run():
        if not _bench_done.wait(seconds):
            print(json.dumps({
                "metric": "images_per_sec_per_chip_inceptionv3_"
                          "featurize[stalled]",
                "value": None, "unit": "images/sec/chip",
                "vs_baseline": None,
                "error": f"bench watchdog: run exceeded {seconds}s"}),
                flush=True)
            os._exit(3)

    threading.Thread(target=run, daemon=True).start()


def main() -> None:
    # FIRST: a wedged bench is exactly the flight recorder's use case —
    # SPARKDL_TPU_FLIGHT=1 must install the SIGUSR2 trigger + span
    # retention before any section that can stall, not at reporting time
    from sparkdl_tpu.obs import flight as obs_flight
    obs_flight.autoarm()
    # compile forensics are part of the bench contract (the "compile"
    # block + the ledger's model-specific compute ceiling both read
    # it) — armed for the whole run, before the first model builds.
    # The AOT cost-analysis pass this enables rides the persistent XLA
    # compilation cache configured below, so big programs compile once.
    from sparkdl_tpu.obs.compile_log import compile_log
    compile_log().arm()
    _start_watchdog()
    import jax

    # persistent XLA cache: repeat bench runs skip the InceptionV3
    # compiles
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()

    from sparkdl_tpu.models.zoo import getModelFunction
    from sparkdl_tpu.runtime.runner import BatchRunner
    from sparkdl_tpu.runtime.sanitize import armed_run_count, sanitize_enabled
    from sparkdl_tpu.utils.measure import (
        measure_device_resident,
        measure_host_copy,
        measure_link,
    )

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    batch_size = 128 if on_tpu else 8
    n_rows = batch_size * (4 if on_tpu else 2)

    model_name = "TestNet" if BENCH_TINY else "InceptionV3"
    mf = getModelFunction(model_name, featurize=True)
    (src_h, src_w, _c), _ = mf.input_signature["image"]
    link = measure_link(32 if on_tpu else (4 if BENCH_TINY else 8))
    # 16 batches: the timed window must amortize per-call dispatch
    # latency
    device = measure_device_resident(mf, batch_size,
                                     n_batches=16 if on_tpu else 2)

    # the host-copy micro-shape: PROOF (RunnerMetrics counters, not
    # assertion) that batch-aligned ship is zero-copy and only the
    # padded tail stages — the ship-side twin of the transfer-strategy
    # measurements
    host_copy = measure_host_copy(mf, batch_size,
                                  n_batches=4 if on_tpu else 2)

    def time_runner(runner, images, batch_size):
        """Warmup, then median of 3 full passes: the median is robust
        to one contended pass without overstating sustained
        throughput."""
        n = len(images)
        runner.run({"image": images[:batch_size]})  # steady-state warmup
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = runner.run({"image": images})
            elapsed = time.perf_counter() - t0
            assert out["features"].shape[0] == n, \
                out["features"].shape
            rates.append(n / elapsed)
        return float(np.median(rates))

    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, size=(n_rows, src_h, src_w, 3),
                          dtype=np.uint8)
    runner = BatchRunner(mf, batch_size=batch_size)
    e2e_ips = time_runner(runner, images, batch_size)

    # packed path: ship small uint8, resize on device (fused). The big
    # in-env lever on the link-bound headline — bytes/image shrinks
    # (150²/299²≈¼) so the ceiling and the measured value lift together.
    from sparkdl_tpu.transformers.utils import deviceResizeModel
    packed_src = (16, 16) if BENCH_TINY else (150, 150)
    images_small = rng.integers(
        0, 255, size=(n_rows,) + packed_src + (3,), dtype=np.uint8)
    packed_ips = time_runner(
        BatchRunner(deviceResizeModel(mf, packed_src),
                    batch_size=batch_size),
        images_small, batch_size)

    # 4:2:0 packed path (VERDICT r4 next #1): planar YCbCr payload at
    # 1.5 B/px — HALF the RGB packed bytes — reconstructed+resized on
    # device fused into the model program.
    from sparkdl_tpu.image.imageIO import rgbToYuv420
    packed_420 = np.stack([rgbToYuv420(im) for im in images_small])
    packed420_ips = time_runner(
        BatchRunner(deviceResizeModel(mf, packed_src,
                                      packedFormat="yuv420"),
                    batch_size=batch_size),
        packed_420, batch_size)

    # NO-resolution-loss 4:2:0 shape: ship 298² planes (even-dims
    # requirement; 1.5 B/px ≈ 133 KB/img, half the 299² RGB payload)
    # and device-resize the 1px up to the model's 299² — the packed
    # option for pipelines that must not trade source resolution.
    # TPU-only: on an explicit CPU run this extra InceptionV3 compile
    # (minutes) would risk the watchdog budget.
    fullres_420_src = (298, 298)
    packed420_fullres_ips = None
    if on_tpu:
        images_298 = rng.integers(
            0, 255, size=(n_rows,) + fullres_420_src + (3,),
            dtype=np.uint8)
        packed_420_fullres = np.stack([rgbToYuv420(im)
                                       for im in images_298])
        packed420_fullres_ips = time_runner(
            BatchRunner(deviceResizeModel(mf, fullres_420_src,
                                          packedFormat="yuv420"),
                        batch_size=batch_size),
            packed_420_fullres, batch_size)

    n_decode = 64 if on_tpu else (12 if BENCH_TINY else 24)
    host_decode_ips = measure_host_decode(
        size=(src_h, src_w), n_images=n_decode)
    # the pipeline decodes at the PACKED size (cheaper resize/pack than
    # 299²) — its decode ceiling must be measured at the same size
    host_decode_ips_packed = measure_host_decode(
        size=packed_src, n_images=n_decode)
    host_decode_ips_420 = measure_host_decode(
        size=packed_src, n_images=n_decode,
        packedFormat="yuv420")

    # the full-pipeline headline: disk → decode → pack(4:2:0) → ship →
    # device reconstruct+resize+featurize, one stream. The utilization
    # ledger (obs/ledger.py) windows EXACTLY this pass: ceilings are
    # injected from the link measurement above (the probe is never
    # paid twice in one process), the baseline snaps right before the
    # pass, and one tick after it publishes the live ledger.util.* /
    # ledger.bound_by gauges the "bound" block and ci.sh gate read.
    from sparkdl_tpu.obs.ledger import ledger as _ledger
    led = _ledger()
    # the model-calibrated compute ceiling (docs/OBSERVABILITY.md):
    # device-resident images/s × the compiled program's cost_analysis
    # FLOPs/image (compile log) = the device's demonstrated FLOP rate
    # ON THIS PROGRAM — the compute lane's roofline denominator, with
    # compute_basis naming it in the ledger verdict. Degrades to None
    # (busy-time attribution) on backends whose cost_analysis returns
    # nothing.
    model_flops = getattr(mf.jitted(), "last_flops", None)
    device_gflops = (
        round(device["ips"] * (model_flops / batch_size) / 1e9, 3)
        if model_flops else None)
    led.ensure_ceilings({"link_h2d_MBps": link["h2d_MBps"],
                         "link_d2h_MBps": link["d2h_MBps"],
                         "device_gflops": device_gflops,
                         "source": "bench.measure_link"})
    led.baseline()
    pipeline = measure_pipeline(mf, packed_src, batch_size,
                                n_images=256 if on_tpu else 24,
                                packedFormat="yuv420")
    pipeline_ips = pipeline["ips"]
    ledger_window = led.tick()

    # the parallel host pipeline's serial-vs-pooled proof on the same
    # corpus (ROADMAP item 3) — AFTER the ledger tick so the measured
    # pass's window covers exactly the headline pipeline pass
    pipeline_overlap = measure_pipeline_overlap(
        mf, packed_src, batch_size,
        n_images=128 if on_tpu else 24, packedFormat="yuv420")

    fidelity = measure_fidelity(mf, packed_src,
                                n_images=32 if on_tpu else 8)

    # online serving shape (docs/SERVING.md): concurrent sub-batch
    # requests coalesced by the ModelServer into full device batches.
    # Sized per platform: an explicit CPU InceptionV3 run is slow, so
    # its serve pass stays at a couple of batches.
    if on_tpu:
        serve_args = dict(n_requests=16, rows_per_request=batch_size // 2)
    elif BENCH_TINY:
        serve_args = dict(n_requests=24, rows_per_request=batch_size // 2)
    else:
        serve_args = dict(n_requests=2, rows_per_request=batch_size // 2,
                          threads=2)
    # the serve pass runs with the request log armed: the "tails"
    # block attributes the measured request p99 across the named
    # phases (queue/coalesce/staging/device/reassembly) from the
    # per-request timelines — tools/ci.sh gates its schema and the
    # ≥95% attribution bar
    serve, tails = measure_serve(mf, batch_size, **serve_args)

    # the closed-loop infeed autotuner (sparkdl_tpu/autotune,
    # docs/PERFORMANCE.md): controller settles (few changes, zero
    # oscillations) and must not lose to the fixed host_async default
    # outside the recorded noise band — tools/ci.sh gates it
    autotune = measure_autotune(mf, batch_size, n_rows=n_rows)

    # the device-resident infeed ring (runtime/runner.py InfeedRing):
    # a repeated-corpus steady pass must ship ZERO bytes (all content
    # hits), re-ship zero, and retrace zero — tools/ci.sh gates it
    ship_ring = measure_ship_ring(mf, batch_size, n_rows=n_rows)

    # the disaggregated input service (sparkdl_tpu/inputsvc/,
    # docs/DATA_SERVICE.md): remote-fleet vs local decode rows/s and
    # the snapshot tier's cold/warm epoch amortization — warm decode
    # busy-seconds must read ~0 (ci.sh's two-process drill gates it)
    input_service = measure_input_service(
        n_rows=512 if BENCH_TINY else 4096)

    # the fleet control plane (sparkdl_tpu/fleet/, docs/SERVING.md):
    # hot-swap latency + output-flip proof, persisted-AOT cold vs warm
    # first-request ms (zero compiles on the warm one), and the live
    # packing decision — ci.sh step 22 gates the cross-process drills
    fleet = measure_fleet()

    # Race the two fused-resize implementations device-resident
    # (VERDICT r4 #7, the transfer-strategy precedent: measured, not
    # asserted): the XLA einsum chain is the library default
    # (ops/infeed.py — it fuses into the model program and shards under
    # GSPMD); the Pallas kernel is TPU-only, so the race runs on real
    # hardware only. The faster one must be the default — a mismatch
    # is reported rather than silently accepted.
    infeed_race = {"einsum_ips": None, "pallas_ips": None,
                   "default_margin_pct": None,
                   "default": "einsum", "default_is_fastest": None}
    if on_tpu:
        m_e = deviceResizeModel(mf, packed_src, use_pallas=False)
        m_p = deviceResizeModel(mf, packed_src, use_pallas=True)
        # INTERLEAVED repeats, per-variant max: a single-shot race
        # confuses drift for a winner
        e_best = p_best = 0.0
        for _ in range(2):
            e_best = max(e_best, measure_device_resident(
                m_e, batch_size, n_batches=16)["ips"])
            p_best = max(p_best, measure_device_resident(
                m_p, batch_size, n_batches=16)["ips"])
        infeed_race["einsum_ips"] = e_best
        infeed_race["pallas_ips"] = p_best
        infeed_race["default_margin_pct"] = round(
            (e_best - p_best) / p_best * 100.0, 2)
        # 1% noise floor: a dead heat must not read as a wrong default
        infeed_race["default_is_fastest"] = e_best >= 0.99 * p_best

    # uint8 NHWC on the wire, at the model's native input size
    image_mb = src_h * src_w * 3 / (1024.0 * 1024.0)
    packed_mb = packed_src[0] * packed_src[1] * 3 / (1024.0 * 1024.0)
    packed420_mb = packed_mb / 2.0  # 1.5 B/px vs 3
    ceiling = link["h2d_MBps"] / image_mb
    ceiling_packed = link["h2d_MBps"] / packed_mb
    ceiling_420 = link["h2d_MBps"] / packed420_mb
    # which stage's own ceiling binds the measured pipeline — derived
    # FROM the ledger's attribute() (obs/ledger.py), not bench-local
    # math: utilization per stage = measured pipeline rate over that
    # stage's own ceiling, verdict = the max-utilization stage (which
    # is exactly the min-ceiling stage — the offline and live verdicts
    # are one code path)
    from sparkdl_tpu.obs.ledger import attribute as ledger_attribute
    stage_ceilings = {"decode": host_decode_ips_420,
                      "link": ceiling_420,
                      "compute": device["ips"]}
    offline_util = {k: (pipeline_ips / v if v else 0.0)
                    for k, v in stage_ceilings.items()}
    offline_verdict = ledger_attribute(offline_util)
    pipeline_bound_by = offline_verdict["bound_by"]

    # unified observability (sparkdl_tpu/obs, docs/OBSERVABILITY.md):
    # the registry snapshot always ships; when SPARKDL_TPU_TRACE=1
    # armed the run, the span timeline exports as Perfetto trace-event
    # JSON (SPARKDL_TPU_TRACE_EXPORT names the path) and ci.sh's obs
    # gate schema-checks it (≥1 span per engine/ship/device lane)
    from sparkdl_tpu.obs import default_registry, stall_watchdog, tracer
    trc = tracer()
    obs_block = {
        "trace_armed": bool(trc.armed),
        "trace_events": None,
        "trace_export": None,
        "trace_dropped": trc.dropped,
        "registry": default_registry().snapshot(),
        # the operability layer's own state (docs/OBSERVABILITY.md):
        # whether the run was stall-monitored and whether any flight
        # bundle was written during it
        "watchdog": stall_watchdog().verdict(),
        "flight": obs_flight.recorder().status(),
    }
    from sparkdl_tpu.obs.request_log import request_log as _rlog
    from sparkdl_tpu.obs.slo import slo_tracker as _slo
    # SLO verdicts + request-log retention state: the same shapes
    # /statusz and the flight bundle carry
    obs_block["slo"] = _slo().status()
    obs_block["request_log"] = _rlog().status()
    # the resilience layer's drill/recovery state (docs/RESILIENCE.md):
    # injection config + per-site counts, retry/shed totals, live
    # circuit verdicts — literally the same renderer /statusz and the
    # flight bundle use, so a bench row and a postmortem cannot drift
    resilience_block = obs_flight.resilience_state()
    if trc.armed:
        trace_path = os.environ.get("SPARKDL_TPU_TRACE_EXPORT",
                                    "/tmp/sparkdl_tpu_trace.json")
        obs_block["trace_events"] = trc.export(trace_path)
        obs_block["trace_export"] = trace_path
    ledger_status = led.status()
    result = {
        # monotonically bumped whenever a key is REMOVED or retyped
        # (additions are compatible); tools/bench_compare.py gates a
        # fresh tiny-bench against the committed round schema so
        # bench-trajectory tracking can't silently drift
        "schema_version": 1,
        "metric": (f"images_per_sec_per_chip_testnet_featurize"
                   f"[{platform},tiny]" if BENCH_TINY else
                   f"images_per_sec_per_chip_inceptionv3_featurize"
                   f"[{platform}]"),
        "value": round(pipeline_ips, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(pipeline_ips / PER_CHIP_TARGET, 3),
        "value_fullres_transfer": round(e2e_ips, 1),
        "vs_baseline_fullres_transfer": round(
            e2e_ips / PER_CHIP_TARGET, 3),
        "headline_shape": ("full pipeline: JPEG files -> native "
                           "decode/pack(yuv420) -> ship -> fused "
                           "device featurize, one stream (r1-r4 "
                           "headlined value_fullres_transfer; see "
                           "note + BASELINE.md)"),
        "device_resident_ips": device["ips"],
        "device_tflops": round(
            device["ips"] * INCEPTION_GFLOPS / 1000.0, 2),
        "vs_baseline_device_resident": round(
            device["ips"] / PER_CHIP_TARGET, 3),
        "link_h2d_MBps": link["h2d_MBps"],
        "link_d2h_MBps": link["d2h_MBps"],
        "host_fed_ceiling_ips": round(ceiling, 1),
        "value_packed": round(packed_ips, 1),
        "vs_baseline_packed": round(packed_ips / PER_CHIP_TARGET, 3),
        "packed_src_hw": list(packed_src),
        "host_fed_ceiling_ips_packed": round(ceiling_packed, 1),
        "value_packed420": round(packed420_ips, 1),
        "vs_baseline_packed420": round(
            packed420_ips / PER_CHIP_TARGET, 3),
        "host_fed_ceiling_ips_packed420": round(ceiling_420, 1),
        "value_packed420_fullres": (
            round(packed420_fullres_ips, 1)
            if packed420_fullres_ips is not None else None),
        "vs_baseline_packed420_fullres": (
            round(packed420_fullres_ips / PER_CHIP_TARGET, 3)
            if packed420_fullres_ips is not None else None),
        "packed420_fullres_src_hw": list(fullres_420_src),
        "host_fed_ceiling_ips_packed420_fullres": round(
            link["h2d_MBps"]
            / (fullres_420_src[0] * fullres_420_src[1] * 1.5
               / (1024.0 * 1024.0)), 1),
        "host_decode_ips": round(host_decode_ips, 1),
        "host_decode_ips_packed": round(host_decode_ips_packed, 1),
        "host_decode_ips_packed420": round(host_decode_ips_420, 1),
        "value_pipeline": round(pipeline_ips, 1),
        "vs_baseline_pipeline": round(pipeline_ips / PER_CHIP_TARGET, 3),
        "pipeline_packed_format": "yuv420",
        # the parallel host pipeline (data/pipeline.py,
        # docs/PERFORMANCE.md "Parallel host pipeline"):
        # serial-vs-pooled ips on one corpus, worker/read-ahead
        # config, and the overlap proof — overlap_ratio =
        # (decode_busy + ship_busy) / wall over the pooled pass,
        # > 1 only when decode genuinely overlaps ship. tools/ci.sh's
        # pipeline gate reads it.
        "pipeline_overlap": pipeline_overlap,
        # host-copy counters: aligned must read 0/0 (the zero-copy hot
        # path); tail stages exactly one partial batch through the
        # persistent pad buffer; pipeline_* are the measured pipeline's
        # own RunnerMetrics over both timed passes
        "host_copy": {
            **host_copy,
            "pipeline_bytes_staged": pipeline["bytes_staged"],
            "pipeline_bytes_copied": pipeline["bytes_copied"],
            "pipeline_transfer_wait_s": pipeline["transfer_wait_s"],
        },
        "fidelity": fidelity,
        "serve": serve,
        "tails": tails,
        "autotune": autotune,
        # the device-resident infeed ring's steady-pass verdict
        # (runtime/runner.py InfeedRing; ci.sh step [18/18] gates
        # zero re-ship / zero steady link bytes / zero retraces)
        "ship_ring": ship_ring,
        # the disaggregated input service + snapshot tier
        # (sparkdl_tpu/inputsvc/, docs/DATA_SERVICE.md): remote vs
        # local decode rows/s by fleet size, snapshot cold vs warm
        # epoch, and the warm-epoch decode-busy ≈ 0 amortization proof
        "input_service": input_service,
        # the fleet control plane's swap/warm-start/packing numbers
        # (sparkdl_tpu/fleet/, docs/SERVING.md "Fleet control plane")
        "fleet": fleet,
        "resilience": resilience_block,
        # compile forensics (docs/OBSERVABILITY.md, obs/compile_log.py):
        # per-function compile counts + wall time, retrace attribution,
        # and the zero-retrace verdict over the whole run — literally
        # the same renderer /statusz and the flight bundle use. A
        # warmed serve pass must show unexpected_retraces == 0 (ci.sh
        # gates it, plus an injected off-ladder shape showing > 0 with
        # the diff naming the argument).
        "compile": obs_flight.compile_state(),
        "device_gflops_ceiling": device_gflops,
        "infeed_race": infeed_race,
        "pipeline_bound_by": pipeline_bound_by,
        "pipeline_stage_ceilings_ips": {
            k: round(v, 1) for k, v in stage_ceilings.items()},
        # the live roofline (obs/ledger.py, docs/PERFORMANCE.md): ONE
        # ledger window over the measured pipeline pass — utilization
        # fractions, the continuous bound_by verdict (same attribute()
        # as pipeline_bound_by above), and the ceilings it divided by;
        # ci.sh gates the schema, the [0,1] bounds, and verdict ==
        # max-utilization stage against the published ledger.util.*
        "bound": {
            **({"bound_by": ledger_window["bound_by"],
                "headroom_pct": ledger_window["headroom_pct"],
                "util": ledger_window["util"],
                "window_s": ledger_window["dt_s"],
                "link_basis": ledger_window["link_basis"],
                "compute_basis": ledger_window["compute_basis"],
                "decode_basis": ledger_window["decode_basis"],
                "ship_MBps": ledger_window["ship_MBps"]}
               if ledger_window is not None else
               {"bound_by": None, "headroom_pct": None, "util": None,
                "window_s": None, "link_basis": None,
                "compute_basis": None, "decode_basis": None,
                "ship_MBps": None}),
            **{k: ledger_status[k] for k in ("windows", "ceilings")},
            "offline": {"bound_by": pipeline_bound_by,
                        "util": {k: round(v, 4)
                                 for k, v in offline_util.items()}},
        },
        "runner_strategy": runner.strategy,
        # whether the runners' ship path ran under the runtime
        # sanitizer's transfer guard (SPARKDL_TPU_SANITIZE=1 —
        # runtime/sanitize.py): True means the zero-copy numbers above
        # were enforced by the JAX runtime, not just counted. Requiring
        # armed_run_count() > 0 (not just the env var) makes a
        # degraded-guard backend report False — ci.sh's schema gate
        # then fails instead of certifying unenforced numbers.
        "sanitize": sanitize_enabled() and armed_run_count() > 0,
        "obs": obs_block,
        "note": ("value IS the full measured pipeline (JPEG files -> "
                 "fused native DCT-prescaled decode/resize/pack to "
                 "planar YCbCr 4:2:0 (1.5 B/px, half the RGB payload; "
                 "standard 4:2:0 sources stream out of libjpeg raw) "
                 "-> ship -> fused on-device chroma-upsample+BT.601+"
                 "resize+featurize, ONE stream) — the north-star's "
                 "own shape, which includes read+decode; rounds 1-4 "
                 "headlined the pre-decoded 299^2 transfer shape, "
                 "continued as value_fullres_transfer. "
                 "pipeline_bound_by names the stage whose own ceiling "
                 "binds the pipeline. "
                 "value_fullres_transfer/value_packed/value_packed420 "
                 "feed pre-decoded arrays (transfer-only shapes); "
                 "device_resident_ips is compute with transfers "
                 "excluded; host_decode_ips uses a textured "
                 "(photo-compressibility) corpus. The fidelity block "
                 "quantifies what the reduced-resolution ship costs "
                 "(CENTERED feature cosine vs its cross-image "
                 "baseline — raw cosine is degenerate under this "
                 "env's random weights; end-accuracy parity within "
                 "0.05 is pinned in test_integration_capstone.py::"
                 "test_packed_ship_fidelity, pixel parity in "
                 "test_ops/test_native)"),
    }
    # The FULL result (every key above — ~4 KB as one line) goes to a
    # file: one JSON line holding everything outgrows the driver's
    # 2,000-char stdout tail window. The
    # LAST stdout line is now a compact headline (<1,200 chars) the
    # driver can always parse, carrying the path to the full result;
    # tools/ci.sh's gates read the file (SPARKDL_TPU_BENCH_RESULT
    # names it; default ./bench_result.json).
    result_path = os.environ.get("SPARKDL_TPU_BENCH_RESULT",
                                 "bench_result.json")
    with open(result_path, "w", encoding="utf-8") as f:
        json.dump(result, f, indent=2, default=str)
    headline = {
        "schema_version": result["schema_version"],
        "metric": result["metric"],
        "value": result["value"],
        "unit": result["unit"],
        "vs_baseline": result["vs_baseline"],
        "value_pipeline": result["value_pipeline"],
        "value_fullres_transfer": result["value_fullres_transfer"],
        "value_packed420": result["value_packed420"],
        "device_resident_ips": result["device_resident_ips"],
        "link_h2d_MBps": result["link_h2d_MBps"],
        "pipeline_bound_by": result["pipeline_bound_by"],
        # the LIVE verdict (ledger window over the measured pipeline
        # pass) with its headroom — the offline ceilings verdict above
        # stays for round-over-round continuity
        "bound_by": result["bound"]["bound_by"],
        "bound_headroom_pct": result["bound"]["headroom_pct"],
        "runner_strategy": result["runner_strategy"],
        "sanitize": result["sanitize"],
        "serve_rows_per_s": result["serve"].get("achieved_rows_per_s"),
        "serve_p99_ms": result["serve"].get("p99_latency_ms"),
        "tails_p99_ms": result["tails"].get("p99_ms"),
        "autotune_converged": result["autotune"].get("converged"),
        # compile forensics: total compiles observed + the zero-
        # retrace verdict (docs/OBSERVABILITY.md)
        "compiles": result["compile"].get("events"),
        "unexpected_retraces": result["compile"].get(
            "unexpected_retraces"),
        "result_path": result_path,
        # a POINTER, not prose: long notes are how a headline outgrows
        # the tail window (tools/ci.sh step 4 gates the size)
        "note": "headline only; full result at result_path",
    }
    line = json.dumps(headline)
    if len(line) > 1200:        # the driver tail window is the contract
        line = json.dumps({k: headline[k] for k in
                           ("schema_version", "metric", "value",
                            "unit", "vs_baseline", "result_path")})
    print(line)
    if _bench_done is not None:
        _bench_done.set()  # disarm the stall watchdog


if __name__ == "__main__":
    sys.exit(main())
