"""JAX set-up in one place: meshes, shard_map and the compile cache.

Every mesh/shard_map construction in this repo (distributed sort, MoE
dispatch, out-of-core driver, examples, tests) goes through these
wrappers, so the axis typing and the compiled-cost shape are chosen once.
Every entry point (chip_smoke.py, examples/, shuffle/worker_main) calls
`enable_compile_cache()` before its first compile.
"""
from __future__ import annotations

import os

import jax

#: The persistent compile cache when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path inside the checkout (the path is part of the cache key,
#: so it never moves with a pid, a tempdir or a timestamp).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives at DEFAULT_CACHE_DIR.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's keyword set."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types, over `devices` (default:
    all of them)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes), axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)
