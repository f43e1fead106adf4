"""The map's spill encode: each run gathered whole from the wave's rows.

`WaveSorter.encode_and_spill` builds every spilled run with one gather of
whole rows from load_wave's interleaved row buffer. These cases pin each
spill object byte for byte to the plain construction,
`records.encode_records(run keys, run ids, payload rows)`, together with
the spill metadata, the reducer offsets, and the `lookup` attribute each
run's map.gather span carries. No device runs: the sorted wave (keys,
ids, per-worker valid counts) is built here in numpy, in the layout the
mesh sort returns, so a two-worker mesh needs no second device.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.external_sort import ExternalSortPlan, WaveSorter
from repro.io import records as rec
from repro.io import staging
from repro.io.backends import MemoryBackend
from repro.obs.events import Tracer
from repro.shuffle.runtime import PhaseTimeline

N = 1 << 10
ID_BASE = (1 << 32) - 4 * N  # high ids: the u32 offset arithmetic

#: Per layout: mesh width and each worker's share of the wave's records.
LAYOUTS = {
    "w1": (1, (N,)),
    "w2_unequal": (2, (3 * N // 4, N // 4)),
    "w2_empty_run": (2, (N, 0)),
}


def _wave(pw: int, ids: str, rng):
    """Interleaved rows [key, id, payload...]: ids ascending from ID_BASE
    row by row (the gensort layout) or the same ids in shuffled rows."""
    rows = rng.integers(0, 1 << 32, (N, 2 + pw), dtype=np.uint64)
    rows = rows.astype("<u4")
    want = np.arange(N, dtype=np.uint32) + np.uint32(ID_BASE)
    rows[:, 1] = want if ids == "contiguous" else rng.permutation(want)
    return rows


def _sorted_wave(rows, counts, rng):
    """The mesh sort's output layout: worker wid's run is the first
    counts[wid] entries of its equal segment, ascending by key; the rest
    of a segment is padding the encode must leave out."""
    order = np.lexsort((rows[:, 1], rows[:, 0]))
    keys, ids = rows[order, 0], rows[order, 1]
    w, seg = len(counts), max(counts)
    sk = rng.integers(0, 1 << 32, w * seg, dtype=np.uint64).astype(np.uint32)
    si = rng.integers(0, 1 << 32, w * seg, dtype=np.uint64).astype(np.uint32)
    at = 0
    for wid, n in enumerate(counts):
        sk[wid * seg : wid * seg + n] = keys[at : at + n]
        si[wid * seg : wid * seg + n] = ids[at : at + n]
        at += n
    return sk, si, np.asarray(counts, np.int32), seg


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("pw", [0, 4, 23])
@pytest.mark.parametrize("ids", ["contiguous", "shuffled"])
def test_spill_runs_match_encode_records(ids, pw, layout):
    rng = np.random.default_rng(0)
    w, counts = LAYOUTS[layout]
    plan = ExternalSortPlan(records_per_wave=N, reducers_per_worker=4,
                            payload_words=pw)
    sorter = WaveSorter(plan, jax.sharding.AbstractMesh((w,), ("w",)), "w")
    rows = _wave(pw, ids, rng)
    sk, si, vcounts, seg = _sorted_wave(rows, counts, rng)

    store = MemoryBackend()
    store.create_bucket("b")
    tracer = Tracer(job="encode")
    timeline = PhaseTimeline(sink=tracer.timeline_sink())
    offsets_out: dict = {}
    with staging.AsyncWriter(plan.max_inflight_writes) as spiller:
        sorter.encode_and_spill(store, "b", 3, sk, si, vcounts, rows,
                                spiller=spiller, timeline=timeline,
                                tag="g3", offsets_out=offsets_out)
        spiller.drain()

    row_of = {int(i): r for r, i in enumerate(rows[:, 1])}
    bounds = sorter.cfg.keyspace.local_reducer_boundaries()
    for wid, n in enumerate(counts):
        run_k = sk[wid * seg : wid * seg + n]
        run_i = si[wid * seg : wid * seg + n]
        sel = np.asarray([row_of[int(i)] for i in run_i], np.int64)
        key = f"{plan.spill_prefix}wave-0003/w-{wid:03d}"
        assert store.get("b", key) == rec.encode_records(
            run_k, run_i, rows[sel, 2:] if pw else None), key
        offsets = np.concatenate(
            ([0], np.searchsorted(run_k, bounds[wid], side="left"), [n]))
        np.testing.assert_array_equal(offsets_out[(3, wid)], offsets)
        assert store.head("b", key).metadata == {
            "records": n, "wave": 3, "worker": wid,
            "reducer_offsets": [int(o) for o in offsets]}

    kind = "offset" if ids == "contiguous" else "argsort"
    gathers = [e for e in tracer.log.events() if e["name"] == "map.gather"]
    assert [e["lookup"] for e in gathers if "lookup" in e] == [kind] * w
