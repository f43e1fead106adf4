"""Pallas TPU kernel: range-partition offsets of sorted keys.

TPU adaptation of the paper's range partitioner (§2.2): the key space
[0, 2^64) is split into R ranges and every record is routed to the
range owner. On TPU the records are already sorted when partitioning happens
(the map task sorts first, §2.3), so partitioning reduces to finding, for
each boundary b_j, the offset of the first key >= b_j — i.e. a vectorized
searchsorted. The slice [offsets[j-1], offsets[j]) of the sorted block is
then exactly the paper's "slice sent to worker j".

The kernel is boundary-generic: it never assumes the equal Indy split.
Sampled quantile boundaries (core/keyspace.sampled_boundaries — the
Daytona-style skew fallback, wired end-to-end by shuffle/recursive) flow
through unchanged, including duplicate boundary values, which simply
yield empty slices. The routing contract — offsets[j] = #{k < b_j}
(searchsorted side="left"), so slice j holds exactly the keys with
b_{j-1} <= k < b_j, the same membership the host-side
RangePartitioner.partition_of computes with side="right" — is pinned
bit-for-bit against `searchsorted_reference` below by
tests/test_shuffle.py's property tests.

Instead of a branchy binary search (log n dependent steps), the kernel
computes offsets[j] = sum_i [key_i < b_j] by streaming the sorted row
through VMEM and accumulating counts — a pure vector-compare + reduce
pipeline at 8x128 lane width with no data-dependent control flow.

Grid: (row, key tile). A row of B keys is laid out as (B/128, 128); each
program loads one (KEY_TILE/128, 128) tile, so a row is never loaded
whole. The boundaries ride in SMEM (scalar prefetch). For each boundary
the program adds the tile's per-lane counts into row j of its (R, 128)
output block, which stays resident across the row's key tiles; the lane
sums are folded outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KEY_TILE = 1 << 16  # keys per grid step (256 KiB of uint32 in VMEM)


def searchsorted_reference(sorted_keys, boundaries):
    """Host oracle for the kernel's contract: (num_blocks, R) int32 with
    out[i, j] = #{k in row i : k < boundaries[j]} — numpy searchsorted
    side="left" per row. The property tests pin the Pallas kernel to this
    bit-for-bit on adversarial boundaries (duplicates, 0, boundary-equal
    keys, all-equal rows)."""
    import numpy as np

    sk = np.asarray(sorted_keys, dtype=np.uint32)
    bs = np.asarray(boundaries, dtype=np.uint32)
    return np.stack([
        np.searchsorted(row, bs, side="left") for row in sk
    ]).astype(np.int32)


def _partition_kernel(bounds_ref, keys_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    keys = keys_ref[...]

    def body(j, carry):
        below = (keys < bounds_ref[j]).astype(jnp.int32)
        out_ref[pl.ds(j, 1), :] += jnp.sum(below, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def partition_offsets_blocks(
    sorted_keys: jax.Array, boundaries: jax.Array, *, interpret: bool
):
    """offsets[i, j] = #{k in row i : k < boundaries[j]}.

    sorted_keys: (num_blocks, B) uint32, rows ascending (sortedness is not
    required for correctness of the count, only for the offsets-as-slices
    interpretation). boundaries: (R,) uint32 ascending, R >= 1. Returns
    (num_blocks, R) int32. `interpret` is the caller's choice (True: the
    Pallas interpreter; False: Mosaic).
    """
    nb, b = sorted_keys.shape
    (r,) = boundaries.shape
    lanes = min(b, 128)
    rows = -(-b // lanes)
    tile_rows = min(rows, max(KEY_TILE // lanes, 1))
    rows = -(-rows // tile_rows) * tile_rows
    if rows * lanes != b:
        # uint32 max is below no boundary, so pad keys are never counted.
        sorted_keys = jnp.pad(sorted_keys, ((0, 0), (0, rows * lanes - b)),
                              constant_values=jnp.uint32(0xFFFFFFFF))
    counts = pl.pallas_call(
        _partition_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, rows // tile_rows),
            in_specs=[pl.BlockSpec((None, tile_rows, lanes),
                                   lambda i, t, bounds: (i, t, 0))],
            out_specs=pl.BlockSpec((None, r, lanes),
                                   lambda i, t, bounds: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nb, r, lanes), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(boundaries, sorted_keys.reshape(nb, rows, lanes))
    return jnp.sum(counts, axis=-1, dtype=jnp.int32)
