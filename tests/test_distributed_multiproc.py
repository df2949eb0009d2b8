"""Two-process ``jax.distributed`` test (VERDICT r1 missing #3).

The reference delegated inter-host behavior to Spark and never tested it
beyond local-mode; this build owns its DCN layer, so multi-process is
exercised for real: two coordinator-joined CPU processes with 4 virtual
devices each form one 8-device global mesh, run a cross-process
collective, and shard one logical DataFrame's partitions disjointly
(reference role: SURVEY §2.5 Spark RPC between hosts).
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_distmp_worker.py")
NUM_PARTITIONS = 5


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env() -> dict:
    """A plain multi-process CPU runtime for the workers (shared
    helper — the same environment the multichip dry run uses)."""
    from sparkdl_tpu.utils.hostenv import sanitized_cpu_env
    return sanitized_cpu_env(pythonpath=REPO_ROOT, n_devices=4)


@pytest.fixture(scope="module")
def worker_results():
    port = _free_port()
    env = _clean_env()
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(i), str(port), str(NUM_PARTITIONS)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO_ROOT) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    results = []
    for out in outs:
        lines = [l for l in out.splitlines() if l.startswith("RESULT ")]
        assert lines, f"no RESULT line in worker output:\n{out[-3000:]}"
        results.append(json.loads(lines[0][len("RESULT "):]))
    return sorted(results, key=lambda r: r["pid"])


def test_global_runtime_topology(worker_results):
    for r in worker_results:
        assert r["process_count"] == 2
        assert r["local_devices"] == 4
        assert r["global_devices"] == 8


def test_cross_process_collective(worker_results):
    # process 0 contributes 0+1+2+3, process 1 contributes 10+11+12+13;
    # both observe the same global sum — proof the psum crossed processes.
    for r in worker_results:
        assert r["psum_total"] == pytest.approx(52.0)


def test_host_shard_indices_disjoint_covering(worker_results):
    a, b = (set(r["shard_indices"]) for r in worker_results)
    assert a.isdisjoint(b)
    assert a | b == set(range(NUM_PARTITIONS))


@pytest.fixture(scope="module", params=[4, 3, "resume"],
                ids=["even-shards", "uneven-shards", "ckpt-resume"])
def streaming_fit_results(request, tmp_path_factory):
    """2-process multi-host STREAMING estimator fit over shared images:
    each host decodes only its shard; gradient sync crosses hosts.
    With 3 partitions over 2 hosts the shards are UNEVEN, so the
    smaller host must cycle its shard to meet the global step quota —
    the collective-alignment path."""
    import keras
    import numpy as np
    from PIL import Image

    resume = request.param == "resume"
    num_partitions = 4 if resume else request.param
    d = tmp_path_factory.mktemp("mhimgs")
    rng = np.random.default_rng(9)
    for i in range(16):
        base = 40 if i % 2 == 0 else 210
        arr = np.clip(rng.normal(base, 15, (8, 8, 3)), 0, 255) \
            .astype(np.uint8)
        Image.fromarray(arr, "RGB").save(d / f"i_{i}.png")

    keras.utils.set_random_seed(7)
    m = keras.Sequential([
        keras.layers.Input((8, 8, 3)),
        keras.layers.Flatten(),
        keras.layers.Dense(2, activation="softmax")])
    model_file = str(d / "m.keras")
    m.save(model_file)

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_distmp_train_worker.py")
    port = _free_port()
    env = _clean_env()
    argv = [str(port), str(d), model_file, str(num_partitions)]
    if resume:
        argv.append(str(tmp_path_factory.mktemp("mhckpt")))
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i)] + argv,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=REPO_ROOT) for i in range(2)]
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
            line = [l for l in out.splitlines()
                    if l.startswith("RESULT ")][0]
            results.append(json.loads(line[len("RESULT "):]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return num_partitions, sorted(results, key=lambda r: r["pid"])


def test_multihost_streaming_fit_identical_models(streaming_fit_results):
    num_partitions, (a, b) = streaming_fit_results
    # round-robin shard sizes (uneven when partitions don't divide)
    assert a["local_partitions"] == (num_partitions + 1) // 2
    assert b["local_partitions"] == num_partitions // 2
    # replicated state stayed in lockstep: same loss history, same
    # final weights on both hosts
    assert len(a["history"]) == 2
    assert a["history"] == pytest.approx(b["history"], rel=1e-6)
    assert np.isfinite(a["weight_digest"])
    assert a["weight_digest"] == pytest.approx(b["weight_digest"],
                                               rel=1e-6)


def test_multihost_cache_decoded_matches_uncached(streaming_fit_results):
    """cacheDecoded multi-host: each host spills only its shard and
    later epochs stream the per-host cache — the replicated state must
    end exactly where the uncached fit ends, on every host."""
    _, results = streaming_fit_results
    a, b = results
    if "cached_history" not in a:
        pytest.skip("cached scenario runs in the non-ckpt params")
    for r in results:
        assert r["cached_history"] == pytest.approx(r["history"],
                                                    rel=1e-6)
        assert r["cached_digest"] == pytest.approx(r["weight_digest"],
                                                   rel=1e-6)
    assert a["cached_digest"] == pytest.approx(b["cached_digest"],
                                               rel=1e-6)


def test_multihost_checkpoint_resume(streaming_fit_results):
    """Interrupted multi-host streaming training (1 epoch saved, budget
    extended to 2) must resume from the per-host checkpoints — resume
    step agreed over DCN — and reproduce the uninterrupted 2-epoch run
    exactly, with identical state on every host."""
    _, results = streaming_fit_results
    a, b = results
    if "resumed_history" not in a:
        pytest.skip("checkpoint scenario runs in the ckpt-resume param")
    for r in results:
        # a silent from-scratch retrain reproduces identical
        # history/weights here (fully deterministic seeds), so the
        # restore itself must be asserted: resumedFrom distinguishes it
        assert r["short_resumed_from"] == 0
        assert r["resumed_from"] == 1
        assert len(r["short_history"]) == 1
        assert len(r["resumed_history"]) == 2
        # epoch 0 was NOT retrained: its loss is the restored history
        assert r["resumed_history"][0] == pytest.approx(
            r["short_history"][0], rel=1e-6)
        # the resumed run ends exactly where the uninterrupted run does
        assert r["resumed_history"] == pytest.approx(r["history"],
                                                     rel=1e-6)
        assert r["resumed_digest"] == pytest.approx(r["weight_digest"],
                                                    rel=1e-6)
    assert a["resumed_digest"] == pytest.approx(b["resumed_digest"],
                                                rel=1e-6)


def test_global_mesh_train_step(worker_results):
    """One DP train step over the pod-wide mesh: the gradient all-reduce
    crossed processes, so both report the identical finite loss."""
    a, b = (r["train_loss"] for r in worker_results)
    assert np.isfinite(a)
    assert a == pytest.approx(b, rel=1e-6)


def test_host_shard_dataframe_partitions_rows(worker_results):
    n_rows = 4 * NUM_PARTITIONS - 1
    a, b = (set(r["rows"]) for r in worker_results)
    assert a and b
    assert a.isdisjoint(b)
    assert a | b == set(range(n_rows))


def test_multihost_dp_inference_matches_single_process(worker_results):
    """Multi-host DP inference (SURVEY §2.4's core strategy at the
    inter-host level): each host featurizes only its shard on its local
    mesh; the union must cover every row exactly once and match a
    single-process run of the same frame bit-for-bit (TestNet's seeded
    params are identical everywhere)."""
    import _distmp_worker as worker

    a, b = worker_results
    got = sorted(tuple(p) for r in (a, b) for p in r["features"])
    xs = [x for x, _ in got]
    n_rows = 4 * NUM_PARTITIONS - 1
    assert xs == list(range(n_rows))  # disjoint, covering, no dupes

    ref = worker.featurize_rows(
        worker.build_image_frame(n_rows, NUM_PARTITIONS))
    for (x, s), (rx, rs) in zip(got, ref):
        assert x == rx
        assert s == pytest.approx(rs, rel=1e-5)
