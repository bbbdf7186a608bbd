"""repro.compat — the JAX surfaces the repo builds on, in one place.

The one target is the installed JAX (0.9.x). What the repo takes from it
with a fixed policy rather than the library default:

  * ``shard_map``: :func:`shard_map` is ``jax.shard_map`` with the
    replication check (``check_vma``) under one repo-wide keyword.
  * ``make_mesh``: ``jax.make_mesh`` defaults to ``AxisType.Explicit`` axes,
    on which gathers from a sharded operand raise ``ShardingTypeError``.
    :func:`make_mesh` builds ``Auto`` axes, which every mesh here assumes.
  * Pallas: :func:`has_pallas_cpu_lowering` is the probe the kernel tier
    ladder consults for the ``pallas-cpu`` tier.
  * :func:`enable_compile_cache` points JAX's persistent compilation cache
    at a fixed directory; each entry point calls it at the top of ``main``.

Engine, kernel and launch code reach these surfaces through here.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = [
    "shard_map",
    "make_mesh",
    "enable_compile_cache",
    "COMPILE_CACHE_DIR",
    "has_pallas_cpu_lowering",
]


def shard_map(f, mesh, in_specs, out_specs, check_replication: bool = True):
    """``jax.shard_map`` with the replication check as ``check_replication``."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_replication,
    )


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    devices: Sequence | np.ndarray | None = None,
) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    shape = tuple(int(s) for s in axis_shapes)
    return jax.make_mesh(
        shape, tuple(axis_names), devices=devices,
        axis_types=(AxisType.Auto,) * len(shape),
    )


# ----------------------------------------------------------------------------
# Persistent compilation cache
# ----------------------------------------------------------------------------

# <checkout>/.jax_cache: fixed, because the directory is part of what a later
# process must find again (a temp or per-run path never hits).
COMPILE_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is left in charge (JAX reads
    it itself) and nothing else is configured. Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`. Entry points call this at the top of
    ``main``; importing ``repro`` never does, so tests keep JAX's defaults.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# ----------------------------------------------------------------------------
# Pallas CPU lowering probe
# ----------------------------------------------------------------------------

# Lazy: probing requires compiling a (tiny) kernel, so it must not run at
# import time. None = not probed yet.
_PALLAS_CPU_LOWERING: bool | None = None


def has_pallas_cpu_lowering() -> bool:
    """True when this JAX can *lower* (not interpret) Pallas on the CPU backend.

    Where JAX has no CPU lowering path for ``pallas_call`` it raises
    ``Only interpret mode is supported on CPU backend``. The kernel tier
    resolver (:mod:`repro.kernels.ops`) consults this once: when it is False
    the ``pallas-cpu`` tier is simply unavailable and dispatch lands on XLA —
    never on silent interpret-mode emulation. Probed by compiling a trivial
    copy kernel the first time it is asked; the answer is cached for the
    process.
    """
    global _PALLAS_CPU_LOWERING
    if _PALLAS_CPU_LOWERING is not None:
        return _PALLAS_CPU_LOWERING
    if jax.default_backend() == "tpu":
        _PALLAS_CPU_LOWERING = False
        return False
    import jax.numpy as jnp
    from jax.experimental import pallas

    def _copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    try:
        out = pallas.pallas_call(
            _copy,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            interpret=False,
        )(jnp.zeros((8, 128), jnp.float32))
        jax.block_until_ready(out)
        _PALLAS_CPU_LOWERING = True
    except Exception:  # the message varies; any failure means no lowering
        _PALLAS_CPU_LOWERING = False
    return _PALLAS_CPU_LOWERING
