"""Computation-environment configuration for the loader stack.

One place for the process-level platform knobs the rest of the package
reads implicitly — float width, backend selection, forced host device
count, XLA flags — plus the *fingerprint* of the resolved platform that
keys every measured artifact (:mod:`repro.core.tune` autotuner
profiles).  Two rules:

* Setters that only take effect before the JAX backend initializes
  (:func:`set_platform`, :func:`set_host_devices`) say so and warn when
  called too late, instead of silently doing nothing.
* ``XLA_FLAGS`` is merged flag-by-flag, never clobbered — a user's
  pre-set flags survive ours and vice versa.

Typical use, before any jax import does real work::

    from repro.core import env
    env.set_host_devices(4)      # 4 forced host devices (sharded loads)
    env.set_platform("cpu")

and afterwards ``env.fingerprint()`` names the configuration —
``linux-x86_64-cpu8-cpu-d4-x32`` — so profiles measured under one
device split or float regime are never served to another.
"""
from __future__ import annotations

import os
import platform as _platform
import re
import warnings
from pathlib import Path
from typing import Dict, Optional

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"

# XLA flags recommended for GPU latency hiding (jax gpu performance
# tips); harmless elsewhere but only applied when the gpu platform is
# selected explicitly.
_GPU_FLAGS = {
    "--xla_gpu_enable_latency_hiding_scheduler": "true",
    "--xla_gpu_triton_gemm_any": "True",
}


# fixed, never temp- or time-derived: a later run of this checkout must
# find what an earlier one cached
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``.jax_cache`` directory of this checkout (git-ignored).  Called by
    entry points (``chip_smoke.py``, ``benchmarks/run.py``), never at
    import and never by tests.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)


def _jax_initialized() -> bool:
    """Whether the JAX backend already committed to a platform/device
    split (late platform/device changes are silently ignored by jax)."""
    import jax
    try:
        return jax._src.xla_bridge._backends != {}
    except AttributeError:       # private layout moved; assume the worst
        return True


def get_xla_flags() -> Dict[str, Optional[str]]:
    """Parse ``XLA_FLAGS`` into a ``{flag: value}`` dict (value ``None``
    for bare flags)."""
    out: Dict[str, Optional[str]] = {}
    for tok in os.environ.get("XLA_FLAGS", "").split():
        name, sep, val = tok.partition("=")
        out[name] = val if sep else None
    return out


def set_xla_flag(name: str, value: Optional[str]) -> None:
    """Merge one flag into ``XLA_FLAGS`` (replacing that flag only)."""
    flags = get_xla_flags()
    flags[str(name)] = None if value is None else str(value)
    os.environ["XLA_FLAGS"] = " ".join(
        k if v is None else f"{k}={v}" for k, v in flags.items())


def enable_x64(flag: bool = True) -> None:
    """Switch the default JAX float/int width to 64 bits (or back).

    The loader stack is int32-native by design (see
    ``build.INT32_OFFSETS_LIMIT``); x64 matters for downstream numerics
    that consume the loaded graphs.  Takes effect immediately.
    """
    import jax
    jax.config.update("jax_enable_x64", bool(flag))


def set_debug_nan(flag: bool = True) -> None:
    """Raise on NaN production in jitted programs (debugging aid)."""
    import jax
    jax.config.update("jax_debug_nans", bool(flag))


def set_platform(name: str = "cpu") -> None:
    """Select the JAX platform ('cpu' | 'gpu' | 'tpu').

    Only effective before the backend initializes; a late call warns.
    Selecting ``gpu`` also merges the latency-hiding XLA flags from the
    jax GPU performance guide into ``XLA_FLAGS``.
    """
    import jax
    if _jax_initialized():
        warnings.warn("set_platform called after the JAX backend "
                      "initialized; the platform will not change",
                      RuntimeWarning, stacklevel=2)
    if name == "gpu":
        for k, v in _GPU_FLAGS.items():
            set_xla_flag(k, v)
    jax.config.update("jax_platform_name", name)


def set_host_devices(n: int) -> None:
    """Force the CPU backend to expose ``n`` devices (the sharded
    loader's mesh width).  Only effective before backend init; clamped
    to the physical core count with a warning, like the cores knob in
    every JAX environment helper."""
    n = int(n)
    cores = os.cpu_count() or 1
    if n > cores:
        warnings.warn(f"only {cores} CPUs available; forcing {cores} "
                      f"host devices instead of {n}",
                      RuntimeWarning, stacklevel=2)
        n = cores
    if _jax_initialized():
        warnings.warn("set_host_devices called after the JAX backend "
                      "initialized; the device count will not change",
                      RuntimeWarning, stacklevel=2)
    set_xla_flag(_DEVICE_COUNT_FLAG, str(max(n, 1)))


def forced_host_devices() -> Optional[int]:
    """The ``--xla_force_host_platform_device_count`` currently in
    ``XLA_FLAGS``, or None when unset (natural device count)."""
    val = get_xla_flags().get(_DEVICE_COUNT_FLAG)
    if val is None:
        return None
    m = re.fullmatch(r"\d+", val)
    return int(m.group()) if m else None


def platform_profile() -> Dict[str, object]:
    """The resolved platform configuration, as data.

    Everything that changes where the streaming loader's throughput
    knee sits: machine + core count (staging bandwidth), backend
    (which XLA lowers the fused parse), device count (XLA splits its
    host threadpool across forced devices), and the float-width regime.
    """
    import jax
    return {
        "system": _platform.system().lower(),
        "machine": _platform.machine(),
        "cpu_count": os.cpu_count() or 1,
        "backend": jax.default_backend(),
        "device_count": forced_host_devices() or jax.device_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }


def fingerprint() -> str:
    """Canonical profile key for measured artifacts (tune profiles):
    ``{system}-{machine}-cpu{N}-{backend}-d{devices}-x{32|64}``."""
    p = platform_profile()
    return (f"{p['system']}-{p['machine']}-cpu{p['cpu_count']}"
            f"-{p['backend']}-d{p['device_count']}"
            f"-x{64 if p['x64'] else 32}")
