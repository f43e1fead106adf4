"""Pallas TPU kernel: indexed k-way merge — the reduce merge on the device.

The paper's reduce task (§2.4) merges R1 spilled runs per output
partition; our streaming reduce fetches bounded chunk windows of each
run and merges one window per emit cycle (shuffle/runtime). Until this
kernel, that merge was host numpy (`merge_fragments`: a stable argsort
over the concatenated packed keys). This module puts the window merge on
the device as a *tournament of pairwise bitonic merges* — the same
network as kernels/merge_sorted.py, extended to carry a third operand:

  keys: uint32   vals: uint32   idx: int32 (window ordinal)

The network compares LEXICOGRAPHICALLY on (key, val, idx). Because `idx`
is each record's position in the concatenated fragment window, the full
triple order is exactly the stable argsort order of the packed
(key<<32|val) keys — ties between equal (key, val) records keep fragment
order, then within-fragment order. That makes the device merge
bit-identical to `merge_fragments` for ANY input (duplicate packed keys
included), and `idx` doubles as the gather index for host-side payload
rows. Padding to power-of-two shapes uses the lex-max record
(0xFFFFFFFF, 0xFFFFFFFF) with idx = window size: real records that
happen to equal the pad key/val still sort BEFORE the pads (smaller
idx), so no fallback path is needed.

Three lowerings of the same network, pinned bit-identical by
tests/test_kernels.py:

  * `merge_sorted_pairs_indexed` — the Pallas kernels of
    kernels/bitonic_sort.py (row tiles in VMEM plus global passes over
    HBM tiles; Mosaic on a real TPU, interpret mode on CPU);
  * the jit'd plain-jnp network — identical math without the pallas_call
    wrapper; on CPU this is the production lowering (XLA-compiled rather
    than Python-interpreted kernel bodies, ~100x faster than interpret
    mode);
  * `kernels/ref.py:merge_kvi_ref` — the lax.sort oracle.

`merge_fragments_device` is the host entry the reduce sink
(shuffle/sort.DeviceMergeReduceOp) calls: it pads the emit window's
fragments to a (K, L) power-of-two grid, runs the tournament, slices the
true count, and gathers payload rows by the merged ordinals.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.bitonic_sort import merge_pairs

PAD_KEY = 0xFFFFFFFF
PAD_VAL = 0xFFFFFFFF
MIN_RUN = 512  # fragment rows pad to >= this: merged rows of >= (8, 128)


def _triple_swap_needed(k0, v0, i0, k1, v1, i1):
    """True where (k0, v0, i0) > (k1, v1, i1) lexicographically."""
    return (k0 > k1) | ((k0 == k1) & ((v0 > v1) | ((v0 == v1) & (i0 > i1))))


def _compare_exchange_idx(keys, vals, idx, dist: int, window: int):
    """One bitonic substage at compare distance `dist` within stage
    `window`, carrying the int32 ordinal as the last lex operand.

    keys/vals/idx: (..., B). Static dist/window (powers of two); leading
    dims broadcast (the jnp lowering batches rows).
    """
    shape = keys.shape
    b = shape[-1]
    groups = b // (2 * dist)
    grouped = shape[:-1] + (groups, 2, dist)
    kr = keys.reshape(grouped)
    vr = vals.reshape(grouped)
    ir = idx.reshape(grouped)
    k0, k1 = kr[..., 0, :], kr[..., 1, :]
    v0, v1 = vr[..., 0, :], vr[..., 1, :]
    i0, i1 = ir[..., 0, :], ir[..., 1, :]

    # Ascending iff the stage window this group falls in has even index
    # (the direction rule of kernels/bitonic_sort.py).
    g = jax.lax.broadcasted_iota(jnp.int32, (groups, 1), 0)
    asc = ((g * (2 * dist)) // window) % 2 == 0

    swap = _triple_swap_needed(k0, v0, i0, k1, v1, i1)
    do = jnp.where(asc, swap, ~swap)

    def weave(a0, a1):
        lo = jnp.where(do, a1, a0)
        hi = jnp.where(do, a0, a1)
        return jnp.stack([lo, hi], axis=-2).reshape(shape)

    return weave(k0, k1), weave(v0, v1), weave(i0, i1)


def _merge_network_idx(keys, vals, idx):
    """Sort a bitonic (..., B) sequence: substages at distance B/2 ... 1,
    one ascending window covering the whole block."""
    b = keys.shape[-1]
    dist = b // 2
    while dist >= 1:
        keys, vals, idx = _compare_exchange_idx(keys, vals, idx, dist, b)
        dist //= 2
    return keys, vals, idx


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_pairs_indexed(
    a_keys: jax.Array, a_vals: jax.Array, a_idx: jax.Array,
    b_keys: jax.Array, b_vals: jax.Array, b_idx: jax.Array,
    *, interpret: bool,
):
    """Merge row i of a_* with row i of b_* (each (n, L), rows sorted
    lexicographically on (key, val, idx)). Returns (keys, vals, idx) of
    shape (n, 2L), each row triple-lex sorted. L must be a power of two.
    `interpret` is the caller's choice (True: the Pallas interpreter;
    False: Mosaic).
    """
    assert a_keys.shape == a_vals.shape == a_idx.shape
    assert a_keys.shape == b_keys.shape == b_vals.shape == b_idx.shape
    return merge_pairs((a_keys, a_vals, a_idx), (b_keys, b_vals, b_idx),
                       interpret=interpret)


def _merge_pairs_body(ak, av, ai, bk, bv, bi):
    """The kernel body as plain batched jnp: concat a ++ reversed(b) per
    row, then one bitonic merge network pass. (n, L) -> (n, 2L)."""
    keys = jnp.concatenate([ak, bk[..., ::-1]], axis=-1)
    vals = jnp.concatenate([av, bv[..., ::-1]], axis=-1)
    idx = jnp.concatenate([ai, bi[..., ::-1]], axis=-1)
    return _merge_network_idx(keys, vals, idx)


def _tournament_body(keys, vals, idx, merge_pairs):
    """(K, L) sorted rows -> one (K*L,) sorted run via log2(K) rounds of
    pairwise merges. K, L static powers of two."""
    k = keys.shape[0]
    while k > 1:
        keys, vals, idx = merge_pairs(keys[0::2], vals[0::2], idx[0::2],
                                      keys[1::2], vals[1::2], idx[1::2])
        k //= 2
    return keys.reshape(-1), vals.reshape(-1), idx.reshape(-1)


@jax.jit
def _tournament_network(keys, vals, idx):
    return _tournament_body(keys, vals, idx, _merge_pairs_body)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _tournament_pallas(keys, vals, idx, *, interpret: bool):
    return _tournament_body(
        keys, vals, idx,
        functools.partial(merge_sorted_pairs_indexed, interpret=interpret))


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def kway_merge_indexed(keys, vals, idx, *, impl: str = "pallas"):
    """Merge K triple-lex-sorted runs -> one sorted run of K*L triples.

    keys, vals: (K, L) uint32; idx: (K, L) int32. K and L powers of two.
    impl:
      "pallas"  — the Pallas kernel tournament (interpret mode on CPU);
      "network" — the identical merge network, jit'd as plain jnp (the
                  fast CPU lowering; bit-identical to "pallas");
      "ref"     — the lax.sort oracle (kernels/ref.merge_kvi_ref).
    """
    k, run = keys.shape
    assert k & (k - 1) == 0, "K must be a power of two"
    if impl == "ref":
        mk, mv, mi = _ref.sort_kvi_ref(keys.reshape(1, -1),
                                       vals.reshape(1, -1),
                                       idx.reshape(1, -1))
        return mk.reshape(-1), mv.reshape(-1), mi.reshape(-1)
    if impl == "network":
        return _tournament_network(jnp.asarray(keys), jnp.asarray(vals),
                                   jnp.asarray(idx))
    assert impl == "pallas", f"unknown impl {impl!r}"
    # The Pallas interpreter on the CPU backend only; Mosaic everywhere
    # else.
    return _tournament_pallas(jnp.asarray(keys), jnp.asarray(vals),
                              jnp.asarray(idx), interpret=_on_cpu())


def _pad_window(frags, total: int):
    """Pack an emit window's fragments into (K, L) power-of-two arrays
    padded with lex-max records whose ordinal is `total` (past every real
    record, so pads always sort last). L is at least MIN_RUN, so every
    merged row fills whole (8, 128) tiles."""
    kp = _next_pow2(len(frags))
    lp = _next_pow2(max(max(f[0].size for f in frags), MIN_RUN))
    keys = np.full((kp, lp), PAD_KEY, np.uint32)
    vals = np.full((kp, lp), PAD_VAL, np.uint32)
    idx = np.full((kp, lp), total, np.int32)
    base = 0
    for r, f in enumerate(frags):
        n = f[0].size
        keys[r, :n] = f[0]
        vals[r, :n] = f[1]
        idx[r, :n] = np.arange(base, base + n, dtype=np.int32)
        base += n
    return keys, vals, idx


def merge_fragments_device(frags, payload_words: int, *,
                           impl: str = "pallas"):
    """Device-backed drop-in for shuffle/runtime.merge_fragments.

    Same contract, bit-identical output: merge already-sorted fragments
    [(keys, ids, payload, k64), ...] into one sorted (keys, ids, payload)
    batch, ties resolved in stable concatenation order (the ordinal
    operand — see module docstring). Payload rows are gathered on the
    host by the merged ordinals. impl "pallas" lowers through the jit'd
    jnp network on CPU (same math as the kernel, XLA-compiled) and the
    real pallas_call elsewhere; "network"/"ref" force those lowerings.
    """
    frags = [f for f in frags if f[3].size]
    if not frags:
        empty = np.empty((0,), np.uint32)
        pw = int(payload_words)
        return empty, empty, (np.empty((0, pw), np.uint32) if pw else None)
    if len(frags) == 1:
        k, i, p, _ = frags[0]
        return k, i, p
    total = sum(f[0].size for f in frags)
    assert total < 2**31, "emit window exceeds int32 ordinal range"
    keys, vals, idx = _pad_window(frags, total)
    if impl == "pallas" and _on_cpu():
        # CPU backend only: the same math as plain jnp, XLA-compiled
        # (see docstring); accelerators run the pallas_call.
        impl = "network"
    mk, mv, mi = kway_merge_indexed(keys, vals, idx, impl=impl)
    mk = np.asarray(mk)[:total]
    mv = np.asarray(mv)[:total]
    payload = None
    if payload_words:
        mi = np.asarray(mi)[:total]
        payload = np.concatenate([f[2] for f in frags])[mi]
    return mk, mv, payload


__all__ = [
    "kway_merge_indexed",
    "merge_fragments_device",
    "merge_sorted_pairs_indexed",
]
