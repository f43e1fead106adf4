"""Public jit'd wrappers over the Pallas sort/merge/partition kernels.

These are the single-device primitives the exoshuffle library composes
(core/sortlib.py). Each op takes `impl`:

  - "pallas":  the TPU kernel (interpret=True on CPU — executes the kernel
               body in Python for bit-exact validation; compiled Mosaic on
               real TPU).
  - "ref":     the pure-jnp oracle from kernels/ref.py (XLA-native sort).
               Used for fast lowering in the 512-device dry-run and as the
               test oracle.

Padding convention: variable-length inputs are padded with the lex-maximal
record (0xFFFFFFFF, 0xFFFFFFFF), which sorts to the tail; callers track true
counts and slice. This mirrors the paper's fixed-size block protocol (map
output slices are padded to the merge-controller block size).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.bitonic_sort import bitonic_sort_blocks
from repro.kernels.merge_sorted import merge_sorted_pairs
from repro.kernels.range_partition import partition_offsets_blocks

PAD_KEY = jnp.uint32(0xFFFFFFFF)
PAD_VAL = jnp.uint32(0xFFFFFFFF)


def _on_cpu() -> bool:
    """The kernels run in the Pallas interpreter on the CPU backend only;
    every other backend compiles them with Mosaic."""
    return jax.default_backend() == "cpu"


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pad_to_pow2(keys: jax.Array, vals: jax.Array):
    """Pad trailing axis to the next power of two with lex-max records."""
    n = keys.shape[-1]
    p = next_pow2(n)
    if p == n:
        return keys, vals, n
    pad = [(0, 0)] * (keys.ndim - 1) + [(0, p - n)]
    keys = jnp.pad(keys, pad, constant_values=PAD_KEY)
    vals = jnp.pad(vals, pad, constant_values=PAD_VAL)
    return keys, vals, n


def sort_kv(keys: jax.Array, vals: jax.Array, *, impl: str = "pallas"):
    """Lexicographic sort along the last axis. Any length; any leading dims.

    Returns arrays of the input shape.
    """
    if impl == "ref":
        return _ref.sort_kv_ref(keys, vals)
    shape = keys.shape
    keys2 = keys.reshape((-1, shape[-1]))
    vals2 = vals.reshape((-1, shape[-1]))
    pk, pv, n = pad_to_pow2(keys2, vals2)
    sk, sv = bitonic_sort_blocks(pk, pv, interpret=_on_cpu())
    return sk[:, :n].reshape(shape), sv[:, :n].reshape(shape)


def merge_kv(a_keys, a_vals, b_keys, b_vals, *, impl: str = "pallas"):
    """Merge two sorted runs (leading dims broadcast over rows)."""
    if impl == "ref":
        return _ref.merge_kv_ref(a_keys, a_vals, b_keys, b_vals)
    shape = a_keys.shape
    run = shape[-1]
    assert run & (run - 1) == 0, "pallas merge needs power-of-two runs"
    ak = a_keys.reshape((-1, run))
    av = a_vals.reshape((-1, run))
    bk = b_keys.reshape((-1, run))
    bv = b_vals.reshape((-1, run))
    mk, mv = merge_sorted_pairs(ak, av, bk, bv, interpret=_on_cpu())
    out_shape = shape[:-1] + (2 * run,)
    return mk.reshape(out_shape), mv.reshape(out_shape)


def kway_merge(keys: jax.Array, vals: jax.Array, *, impl: str = "pallas"):
    """Merge K sorted runs -> one sorted run.

    keys, vals: (..., K, L) with each (..., k, :) row lex-sorted. K, L powers
    of two. Returns (..., K*L). This is the paper's merge/reduce task: a
    tournament of pairwise bitonic merges, log2(K) rounds.
    """
    shape = keys.shape
    k, run = shape[-2], shape[-1]
    assert k & (k - 1) == 0, "K must be a power of two"
    keys = keys.reshape((-1, k, run))
    vals = vals.reshape((-1, k, run))
    while k > 1:
        a_k, b_k = keys[:, 0::2], keys[:, 1::2]
        a_v, b_v = vals[:, 0::2], vals[:, 1::2]
        nb = a_k.shape[0] * a_k.shape[1]
        mk, mv = merge_kv(
            a_k.reshape(nb, run),
            a_v.reshape(nb, run),
            b_k.reshape(nb, run),
            b_v.reshape(nb, run),
            impl=impl,
        )
        k //= 2
        run *= 2
        keys = mk.reshape((-1, k, run))
        vals = mv.reshape((-1, k, run))
    out_shape = shape[:-2] + (shape[-2] * shape[-1],)
    return keys.reshape(out_shape), vals.reshape(out_shape)


def partition_offsets(sorted_keys: jax.Array, boundaries: jax.Array, *, impl: str = "pallas"):
    """offsets[..., j] = #{keys < boundaries[j]} along the last axis."""
    if impl == "ref":
        return _ref.partition_offsets_ref(sorted_keys, boundaries)
    shape = sorted_keys.shape
    if boundaries.shape[0] == 0:
        return jnp.zeros(shape[:-1] + (0,), jnp.int32)
    keys2 = sorted_keys.reshape((-1, shape[-1]))
    out = partition_offsets_blocks(keys2, boundaries, interpret=_on_cpu())
    return out.reshape(shape[:-1] + (boundaries.shape[0],))


@functools.partial(jax.jit, static_argnames=("impl",))
def sort_kv_jit(keys, vals, impl: str = "pallas"):
    return sort_kv(keys, vals, impl=impl)
