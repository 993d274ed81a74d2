"""Pre-jax-init forced-host-device plumbing, shared by the launchers.

jax locks the device count on first backend init, so any CLI that wants
a CPU-emulated multi-device fleet must append
``--xla_force_host_platform_device_count`` to ``XLA_FLAGS`` *before*
importing jax.  This module imports only ``os``/``sys`` (and the empty
``repro``/``repro.launch`` package inits), so launchers can safely call
:func:`force_host_devices` as their first statement —
``repro.launch.sweep``, ``repro.launch.train`` and
``scripts/bench_el.py`` all route through here instead of keeping
hand-rolled copies in sync.  (``repro.launch.dryrun`` keeps its own
env-var preamble: it needs 512 placeholder devices unconditionally.)

:func:`use_compile_cache` sits beside it: the launchers call it (never
``import repro``), so each entry point reuses compiled programs across
processes while the tests keep JAX's defaults.
"""

from __future__ import annotations

import os
import sys
from typing import Sequence

#: the checkout root (``src/repro/launch/hostdev.py`` -> three levels up)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def force_host_devices(flag: str = "--mesh", *,
                       skip: Sequence[str] = ("none",),
                       env: str = "REPRO_SWEEP_DEVICES",
                       default: str = "4",
                       count_from_flag: bool = False,
                       always: bool = False) -> None:
    """Append the forced host-device count when ``flag`` asks for it.

    Scans ``sys.argv`` for ``flag`` (both ``--flag value`` and
    ``--flag=value`` spellings).  When its value is present and not in
    ``skip`` — or unconditionally with ``always=True`` — the device
    count is taken from the flag itself (``count_from_flag=True``, e.g.
    ``--devices 8``) or from the ``env`` variable (default ``4``).
    MUST run before jax initializes its backends.
    """
    val = None
    for i, arg in enumerate(sys.argv):
        if arg == flag and i + 1 < len(sys.argv):
            val = sys.argv[i + 1]
        elif arg.startswith(flag + "="):
            val = arg.split("=", 1)[1]
    if val is None or val in skip:
        if not always:
            return
    n = val if (count_from_flag and val is not None) \
        else os.environ.get(env, default)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=" + n)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX already reads it
    and nothing is changed here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — fixed because the path is part of the
    cache key, so a directory that moved between runs would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
