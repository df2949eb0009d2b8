"""Measurement primitives (``chip_smoke.py``, ``obs/ledger.py``).

One home for the timing methodology: JAX dispatch is asynchronous, so
every timed region ends in a tiny DEPENDENT readback of its result.
"""

from __future__ import annotations

import time

import numpy as np


def sync_readback(x) -> float:
    """Force completion of everything ``x`` depends on via a 1-element
    dependent readback."""
    import jax.numpy as jnp
    return float(jnp.reshape(x, (-1,))[0].astype(jnp.float32))


def measure_link(n_mb: int) -> dict:
    """Host↔device bandwidth in MB/s: ``device_put`` timed against a
    dependent 1-element readback (the sum can't run before the transfer
    lands), then ``device_get`` of the resident buffer. The untimed
    first pass compiles the full-shape sum — a smaller warm-up shape
    would leave that compile inside the timed upload."""
    import jax

    x = np.random.default_rng(0).integers(
        0, 255, size=(n_mb * 1024 * 1024,), dtype=np.uint8)
    sync_readback(jax.device_put(x).sum())  # compile + warm the path
    t0 = time.perf_counter()
    d = jax.device_put(x)
    sync_readback(d.sum())
    up = time.perf_counter() - t0
    t0 = time.perf_counter()
    h = jax.device_get(d)
    down = time.perf_counter() - t0
    assert h[0] == x[0]
    return {"h2d_MBps": round(n_mb / up, 1),
            "d2h_MBps": round(n_mb / down, 1)}
