"""Environment for CPU-only JAX subprocesses.

A chip belongs to one process, so a child started by a process that may
hold it (virtual-device meshes, multi-process jax.distributed tests, the
multichip dry run) is pinned to the CPU backend. One shared helper so
every spawner prepares the same environment.
"""

from __future__ import annotations

import os
from typing import Dict, Optional


def sanitized_cpu_env(pythonpath: Optional[str] = None,
                      n_devices: Optional[int] = None) -> Dict[str, str]:
    """A copy of os.environ prepared for a CPU-only JAX subprocess:
    ``JAX_PLATFORMS=cpu``, ``pythonpath`` (when given) prepended to
    PYTHONPATH so the child imports this checkout, and — when
    ``n_devices`` is given — the virtual host-device-count XLA flag
    (replacing any inherited one)."""
    env = dict(os.environ)
    if pythonpath is not None:
        inherited = env.get("PYTHONPATH")
        env["PYTHONPATH"] = (pythonpath if not inherited
                             else pythonpath + os.pathsep + inherited)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count")]
        flags.append(
            f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env
