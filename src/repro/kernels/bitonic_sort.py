"""Pallas TPU kernels: the bitonic sorting network over (key, val[, idx]) records.

This is the TPU adaptation of the paper's map-task sort (§2.3: "we first
... sort the input data in memory"). The paper uses a serial comparison sort
in C++ on a CPU core; a serial quicksort cannot use the TPU's 8x128 vector
lanes, so we replace it with a *bitonic sorting network*: O(n log^2 n)
compare-exchanges, every one of them a full-width vector op.

Layout. A row of M records (M a power of two) is laid out as an
(M/128, 128) sublane x lane tile, record i at (i // 128, i % 128), one
array per operand (keys, vals, and for the indexed merge the int32
ordinal). The compare-exchange at distance d pairs record i with i ^ d.
Inside a VMEM tile the partner is a static rotation: along the lanes for
d < 128 and along the sublanes for d >= 128 (`pltpu.roll` by d or by
size - d, chosen per element by bit d of its index). Directions come from
iota masks. No 1-D reshape, gather or reversal ever runs inside a kernel.

Two kernels run the network:

  * the local kernel sorts or merges one VMEM tile of T records: every
    stage whose distance is below T, all unrolled in one kernel body;
  * the global pass runs one stage at distance d >= T as a grid over HBM
    tiles: the row is viewed as (groups, 2, d/128, 128), and each program
    compare-exchanges a tile of the lower half with the same tile of the
    upper half.

A full sort of a row is the local sort of every tile, then, for each
window 2T .. M, the global passes at distances window/2 .. T followed by
one local merge of the tiles. The stage direction is bit `window` of the
record's index within its row, so a row of any power-of-two length sorts
with a tile of any smaller power of two: one network, tiled.

VMEM budget: the local kernel holds (in + out) x operands x T x 4 bytes,
double-buffered, plus the network's temporaries. VMEM_TILE = 2^13
records keeps that under 1 MiB, well inside the 16 MiB scoped VMEM of a
TPU v5e core; the global pass moves the same tile size. The local kernel
unrolls every stage, so its compile time grows with the tile: about 3 s
at 2^13 and a minute at 2^16 for a v5e target.

Sorting is LEXICOGRAPHIC over the operands in order: deterministic
output, exact-match testable against kernels/ref.py. Two records the
network finds equal agree on every operand, so an exchange between them
is a no-op and any correct network gives the same bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_TILE = 1 << 13  # records per VMEM tile (local kernel and global pass)


def _lex_less(a, b):
    """True where tuple a < tuple b lexicographically (first operand
    most significant)."""
    lt = a[-1] < b[-1]
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        lt = (x < y) | ((x == y) & lt)
    return lt


def _row_layout(m: int) -> tuple[int, int]:
    lanes = min(m, LANES)
    return m // lanes, lanes


def _local_kernel(*refs, stages, row_len: int):
    """Run `stages` [(window, dist), ...], every dist below the tile, on
    one (rows, lanes) tile of each operand."""
    n_ops = len(refs) // 2
    xs = [r[...] for r in refs[:n_ops]]
    rows, lanes = xs[0].shape
    # Row index within the whole array row: the tile's offset has no bits
    # below the tile, so it only matters for windows of the tile's size
    # and above.
    row = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
           + pl.program_id(1) * rows)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def bit(stride):
        return ((lane & stride) if stride < lanes
                else (row & (stride // lanes))) != 0

    for window, dist in stages:
        if dist < lanes:
            axis, size, shift = 1, lanes, dist
        else:
            axis, size, shift = 0, rows, dist // lanes
        upper = bit(dist)
        partner = [jnp.where(upper, pltpu.roll(x, shift, axis),
                             pltpu.roll(x, size - shift, axis)) for x in xs]
        # The lower element of an ascending pair keeps the smaller record;
        # the descending windows and the upper elements keep the larger.
        keep_max = upper if window >= row_len else upper != bit(window)
        take = _lex_less(partner, xs) != keep_max
        xs = [jnp.where(take, p, x) for p, x in zip(partner, xs)]
    for r, x in zip(refs[n_ops:], xs):
        r[...] = x


def _global_kernel(*refs, window: int, dist: int, groups: int,
                   row_len: int):
    """One stage at distance `dist` (>= the tile) on a (2, rows, lanes)
    block: the tile of the lower half and its partner in the upper."""
    n_ops = len(refs) // 2
    lo = [r[0] for r in refs[:n_ops]]
    hi = [r[1] for r in refs[:n_ops]]
    swap = _lex_less(hi, lo)
    if window < row_len:
        group_start = (pl.program_id(0) % groups) * (2 * dist)
        swap = swap != ((group_start & window) != 0)
    for r, a, b in zip(refs[n_ops:], lo, hi):
        r[0] = jnp.where(swap, b, a)
        r[1] = jnp.where(swap, a, b)


def _params(n_grid: int):
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * n_grid)


def _run_local(xs, stages, *, tile: int, row_len: int, interpret: bool):
    n, rows, lanes = xs[0].shape
    tile_rows = min(rows, tile // lanes)
    spec = pl.BlockSpec((None, tile_rows, lanes), lambda i, t: (i, t, 0))
    return pl.pallas_call(
        functools.partial(_local_kernel, stages=tuple(stages),
                          row_len=row_len),
        grid=(n, rows // tile_rows),
        in_specs=[spec] * len(xs),
        out_specs=tuple([spec] * len(xs)),
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs),
        compiler_params=_params(2),
        interpret=interpret,
    )(*xs)


def _run_global(xs, window: int, dist: int, *, tile: int, row_len: int,
                interpret: bool):
    n, rows, lanes = xs[0].shape
    drows = dist // lanes
    groups = rows // (2 * drows)
    tile_rows = tile // lanes
    shaped = [x.reshape(n * groups, 2, drows, lanes) for x in xs]
    spec = pl.BlockSpec((None, 2, tile_rows, lanes),
                        lambda g, c: (g, 0, c, 0))
    out = pl.pallas_call(
        functools.partial(_global_kernel, window=window, dist=dist,
                          groups=groups, row_len=row_len),
        grid=(n * groups, drows // tile_rows),
        in_specs=[spec] * len(xs),
        out_specs=tuple([spec] * len(xs)),
        out_shape=tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                        for x in shaped),
        compiler_params=_params(2),
        interpret=interpret,
    )(*shaped)
    return [o.reshape(n, rows, lanes) for o in out]


def _check_rows(xs):
    n, m = xs[0].shape
    assert all(x.shape == (n, m) for x in xs), [x.shape for x in xs]
    assert m & (m - 1) == 0, f"row length {m} must be a power of two"
    return n, m


def sort_rows(xs, *, interpret: bool, tile: int = VMEM_TILE):
    """Sort every row of the (n, M) operands `xs` lexicographically.

    M is a power of two; rows longer than `tile` run as local tile sorts
    plus global passes (module docstring)."""
    n, m = _check_rows(xs)
    rows, lanes = _row_layout(m)
    t = min(tile, m)
    kw = dict(tile=t, row_len=m, interpret=interpret)
    ys = [x.reshape(n, rows, lanes) for x in xs]
    ys = _run_local(ys, [(w, d) for w in _pow2s(2, t)
                         for d in _pow2s(1, w // 2)[::-1]], **kw)
    for w in _pow2s(2 * t, m):
        for d in _pow2s(t, w // 2)[::-1]:
            ys = _run_global(ys, w, d, **kw)
        ys = _run_local(ys, [(w, d) for d in _pow2s(1, t // 2)[::-1]], **kw)
    return tuple(y.reshape(n, m) for y in ys)


def merge_pairs(a, b, *, interpret: bool, tile: int = VMEM_TILE):
    """Merge row i of the sorted operands `a` with row i of `b` (each
    (n, L)) into (n, 2L) sorted rows: a ++ reversed(b) is bitonic, and the
    merge half of the network (distances L .. 1) sorts it. The reversal
    is an XLA copy in HBM, outside the kernels."""
    xs = [jnp.concatenate([x, y[:, ::-1]], axis=1) for x, y in zip(a, b)]
    n, m = _check_rows(xs)
    rows, lanes = _row_layout(m)
    t = min(tile, m)
    kw = dict(tile=t, row_len=m, interpret=interpret)
    ys = [x.reshape(n, rows, lanes) for x in xs]
    for d in _pow2s(t, m // 2)[::-1]:
        ys = _run_global(ys, m, d, **kw)
    ys = _run_local(ys, [(m, d) for d in _pow2s(1, t // 2)[::-1]], **kw)
    return tuple(y.reshape(n, m) for y in ys)


def _pow2s(lo: int, hi: int) -> list[int]:
    """Powers of two lo, 2lo, ..., hi (empty when hi < lo)."""
    out = []
    while lo <= hi:
        out.append(lo)
        lo *= 2
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitonic_sort_blocks(keys: jax.Array, vals: jax.Array, *, interpret: bool):
    """Sort each row of (num_blocks, B) (key, val) pairs independently.

    B must be a power of two. Returns (sorted_keys, sorted_vals), each row
    lexicographically ascending. `interpret` is the caller's choice:
    True runs the kernel bodies in the Pallas interpreter (CPU tests),
    False compiles them with Mosaic.
    """
    assert keys.ndim == 2 and keys.shape == vals.shape
    return sort_rows((keys, vals), interpret=interpret)
