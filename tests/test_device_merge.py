"""ISSUE-7 acceptance gates: device-resident reduce merge + pipelined map.

The tentpole contract, pinned end to end:

  * DeviceMergeReduceOp (plan.reduce_merge_impl="device") must produce
    output byte- and etag-identical to the numpy merge backend at
    W in {1, 4} and parallel_reducers in {1, 4}, including under an
    injected worker kill — the merge kernel swap must be invisible in
    the bytes.
  * The pipelined map executor (plan.map_pipeline, on by default) must
    also be byte-invisible, while its staged spans (map.decode /
    map.device_sort / map.encode) and the reduce.device_merge span show
    up in phase_seconds so the overlap is observable.
  * runtime.merge_fragments' ordered fast path (no live interleave ->
    concatenation IS the merge) must be bit-identical to the argsort
    path, boundary ties included.

Sort runs execute in subprocesses (8 host devices) via helpers.
"""
import numpy as np

from helpers import run_with_devices


# ---------------------------------------------------------------------------
# merge_fragments ordered fast path (pure numpy — no devices)
# ---------------------------------------------------------------------------


def _frag(keys, ids, pw=1):
    keys = np.asarray(keys, np.uint32)
    ids = np.asarray(ids, np.uint32)
    k64 = keys.astype(np.uint64) << np.uint64(32) | ids.astype(np.uint64)
    order = np.argsort(k64, kind="stable")
    payload = (ids.reshape(-1, 1).repeat(pw, axis=1).astype(np.uint32)
               if pw else None)
    return (keys[order], ids[order],
            payload[order] if pw else None, k64[order])


def _argsort_merge(frags, pw):
    """The pre-fast-path body, verbatim: the oracle the fast path must
    reproduce bit-for-bit."""
    frags = [f for f in frags if f[3].size]
    k64 = np.concatenate([f[3] for f in frags])
    order = np.argsort(k64, kind="stable")
    keys = np.concatenate([f[0] for f in frags])[order]
    ids = np.concatenate([f[1] for f in frags])[order]
    payload = (np.concatenate([f[2] for f in frags])[order] if pw else None)
    return keys, ids, payload


def _check_identical(frags, pw=1):
    from repro.shuffle.runtime import merge_fragments

    got = merge_fragments(frags, pw)
    want = _argsort_merge(frags, pw)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    if pw:
        np.testing.assert_array_equal(got[2], want[2])
    return got


def test_merge_fragments_single_live_fragment_copies_through():
    # Emit windows where every fragment but one is drained: the common
    # tail of a skewed partition. Empty fragments are filtered, leaving
    # one live run -> the len==1 copy-through.
    frags = [_frag([], []), _frag([5, 9, 9], [1, 0, 2]), _frag([], [])]
    k, i, p = _check_identical(frags)
    np.testing.assert_array_equal(k, [5, 9, 9])


def test_merge_fragments_non_interleaving_fast_path():
    # Live fragments whose key ranges do not interleave: concatenation
    # is the merge. Includes a boundary TIE on the packed (key, id)
    # between fragment ends — fragment order must win, exactly as the
    # stable argsort orders it.
    frags = [
        _frag([1, 2, 3], [7, 7, 7]),
        _frag([3, 4], [7, 9]),   # head (3, 7) ties frag 0's tail (3, 7)
        _frag([4, 10], [9, 0]),  # head (4, 9) ties frag 1's tail
    ]
    k, i, p = _check_identical(frags)
    np.testing.assert_array_equal(k, [1, 2, 3, 3, 4, 4, 10])


def test_merge_fragments_interleaved_still_argsorts():
    # Control: genuinely interleaved fragments must NOT take the fast
    # path's concatenation order (which would be wrong) — output equals
    # the stable argsort merge.
    rng = np.random.default_rng(11)
    frags = [_frag(rng.integers(0, 50, 40, dtype=np.uint32),
                   rng.integers(0, 4, 40, dtype=np.uint32))
             for _ in range(3)]
    got = _check_identical(frags)
    pairs = got[0].astype(np.uint64) << np.uint64(32) | got[1]
    assert (np.diff(pairs.astype(np.int64)) >= 0).all()


def test_merge_fragments_fast_path_no_payload():
    frags = [_frag([1], [1], pw=0), _frag([2], [2], pw=0)]
    k, i, p = _check_identical(frags, pw=0)
    assert p is None


# ---------------------------------------------------------------------------
# end-to-end: device merge + pipelined map (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

SETUP = """
import dataclasses
import tempfile
import numpy as np
from repro.core.external_sort import ExternalSortPlan
from repro.core.compat import make_mesh
from repro.data import gensort, valsort
from repro.io.object_store import ObjectStore
from repro.shuffle.executor import ClusterPlan
from repro.shuffle.sort import sort_shuffle_job

mesh = make_mesh((8,), ("w",))
plan = ExternalSortPlan(
    records_per_wave=1 << 13,
    num_rounds=2,
    reducers_per_worker=2,
    payload_words=2,
    impl="ref",
    input_records_per_partition=1 << 12,
    output_part_records=1 << 11,
    store_chunk_bytes=16 << 10,
    parallel_reducers=2,
    reduce_memory_budget_bytes=64 << 10,
)
N = 1 << 15
store = ObjectStore(tempfile.mkdtemp(prefix="device-merge-test-"))
store.create_bucket("sort")
in_ck, _ = gensort.write_to_store(
    store, "sort", plan.input_prefix, N,
    plan.input_records_per_partition, plan.payload_words)

def layout():
    return [(m.key, m.etag, m.size, m.parts)
            for m in store.list_objects("sort", plan.output_prefix)]

def run(p, **kw):
    return sort_shuffle_job(store, "sort", mesh=mesh, axis_names="w",
                            plan=p).run(**kw)
"""


def test_device_merge_byte_identical_across_schedules():
    # The acceptance gate: reduce_merge_impl="device" output is byte-
    # and etag-identical to the numpy merge at parallel_reducers in
    # {1, 4}, W in {1, 4}, and under a worker kill — and valsort-clean.
    run_with_devices(SETUP + """
rep0 = run(plan, workers=0)  # numpy merge baseline
want = layout()
assert len(want) == 16

for par in (1, 4):
    p_dev = dataclasses.replace(plan, reduce_merge_impl="device",
                                parallel_reducers=par)
    rep = run(p_dev, workers=0)
    assert layout() == want, f"device merge P={par} changed output bytes"
    assert rep.phase_seconds.get("reduce.device_merge", 0) > 0, (
        rep.phase_seconds)

p_dev = dataclasses.replace(plan, reduce_merge_impl="device")
for W in (1, 4):
    crep = run(p_dev, workers=W)
    assert layout() == want, f"device merge W={W} changed output bytes"
    assert crep.num_cluster_workers == W and not crep.failed_workers

crep = run(p_dev, cluster=ClusterPlan(num_workers=4,
                                      fail_after_tasks={1: 2}))
assert layout() == want, "device merge under worker kill changed bytes"
assert crep.failed_workers == ["w1"] and crep.reexecuted_tasks >= 1

val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert val.ok and val.total_records == N, val
print("OK")
""", timeout=900)


def test_map_pipeline_byte_identical_and_staged_spans():
    # The pipelined map executor (default-on) must not change a byte vs
    # the monolithic path, and must surface the staged spans, each
    # recorded once (no map.compute mirror of map.device_sort). The
    # monolithic path keeps its original span shape (map.compute, no
    # map.decode/device_sort/encode).
    run_with_devices(SETUP + """
rep_mono = run(dataclasses.replace(plan, map_pipeline=False), workers=0)
want = layout()
ps = rep_mono.phase_seconds
assert ps.get("map.compute", 0) > 0
for k in ("map.decode", "map.device_sort", "map.encode"):
    assert k not in ps, (k, ps)

rep_pipe = run(plan, workers=0)
assert layout() == want, "map_pipeline changed output bytes"
ps = rep_pipe.phase_seconds
for k in ("map.decode", "map.device_sort", "map.encode", "map.spill",
          "map.gather", "map.pack", "map.device_lock", "map.h2d",
          "map.sort", "map.d2h"):
    assert ps.get(k, 0) > 0, (k, ps)
assert "map.compute" not in ps, ps
# The stage spans split their stage's time, never add to it.
assert ps["map.gather"] + ps["map.pack"] <= ps["map.encode"], ps
assert (ps["map.device_lock"] + ps["map.h2d"] + ps["map.sort"]
        + ps["map.d2h"] <= ps["map.device_sort"]), ps

# pipelined + device merge together, on a cluster
p_both = dataclasses.replace(plan, reduce_merge_impl="device")
crep = run(p_both, workers=2)
assert layout() == want, "pipeline+device cluster run changed bytes"
ps = crep.report.phase_seconds
for k in ("map.decode", "map.device_sort", "map.encode",
          "reduce.device_merge"):
    assert ps.get(k, 0) > 0, (k, ps)
assert crep.spans_dropped == 0
print("OK")
""", timeout=900)
