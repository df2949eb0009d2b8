"""Where JAX's persistent compilation cache lives for this checkout's
scripts (chip_smoke.py, benchmarks/run.py, the multi-process test workers).

A cache is only found again at the same path, so the path is never a
temp name, a pid or a time: whoever runs the scripts places it with
``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable itself, and
then no code here sets a directory — and otherwise it is
``<checkout>/.jax_cache`` (git-ignored), derived from this file's own
location.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CONFIG_OPTION = "jax_compilation_cache_dir"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place the persistent compilation cache (module docstring) and
    return the directory in effect. Call before the first compile."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update(CONFIG_OPTION, DEFAULT_DIR)
    return DEFAULT_DIR
