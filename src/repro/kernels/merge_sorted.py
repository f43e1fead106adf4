"""Pallas TPU kernel: bitonic merge of two lex-sorted (key, val) runs.

TPU adaptation of the paper's merge tasks (§2.3: a merge task merges W
already-sorted map blocks; §2.4: a reduce task merges R1 spilled runs). The
paper's C++ merger is a serial k-way heap merge — O(n log k) comparisons but
fully sequential and branchy, which is hostile to the TPU VPU. We instead
use the classic *bitonic merge network*: concatenating an ascending run with
a reversed (descending) run yields a bitonic sequence, which one log2(2L)
pass of compare-exchanges sorts completely. k-way merging becomes a
tournament of pairwise merges (log2 k rounds), each round fully
data-parallel — see kernels/ops.py:kway_merge.

The network and its tiling are kernels/bitonic_sort.py's: each merged
row of 2L records is laid out as (2L/128, 128) tiles, distances of a VMEM
tile and above run as global passes over HBM tiles, the rest in one local
kernel per tile. Static power-of-two shapes throughout.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.bitonic_sort import merge_pairs


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_sorted_pairs(
    a_keys: jax.Array,
    a_vals: jax.Array,
    b_keys: jax.Array,
    b_vals: jax.Array,
    *,
    interpret: bool,
):
    """Merge row i of a_* with row i of b_* (each (n, L), rows lex-sorted).

    Returns (keys, vals) of shape (n, 2L), each row lex-sorted ascending.
    L must be a power of two. `interpret` is the caller's choice (True:
    the Pallas interpreter; False: Mosaic).
    """
    assert a_keys.shape == a_vals.shape == b_keys.shape == b_vals.shape
    return merge_pairs((a_keys, a_vals), (b_keys, b_vals),
                       interpret=interpret)
