"""Out-of-core external sort, end-to-end through the object store.

The CloudSort loop of examples/cloudsort_oocore.py at test scale, on 8
subprocess host devices: gensort -> store -> map waves (chunked GETs) ->
spill -> ranged-GET reduce merge -> multipart upload -> valsort from the
store, with request-accounting assertions on every leg.
"""
import pytest

from helpers import run_with_devices

SETUP = """
import tempfile
import numpy as np
import jax, jax.numpy as jnp
from repro.core.external_sort import ExternalSortPlan, external_sort
from repro.data import gensort, valsort
from repro.io.object_store import ObjectStore

from repro.core.compat import make_mesh
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
)
N = 1 << 15  # 4 waves -> 4x out-of-core oversubscription
store = ObjectStore(tempfile.mkdtemp(prefix="extsort-test-"))
store.create_bucket("sort")
in_ck, nparts = gensort.write_to_store(
    store, "sort", plan.input_prefix, N,
    plan.input_records_per_partition, plan.payload_words)
"""


def test_external_sort_valsort_gate():
    run_with_devices(SETUP + """
rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
assert rep.total_records == N
assert rep.num_waves == 4 and rep.num_workers == 8
assert rep.oversubscription >= 4.0  # dataset never fits one wave
assert rep.spill_objects == 4 * 8 and rep.output_objects == 16

# the paper's three valsort gates, streamed back out of the store
val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert val.ok, val
assert val.total_records == N
print("OK")
""")


def test_request_accounting_matches_protocol():
    run_with_devices(SETUP + """
gen_stats = store.stats_snapshot()
assert gen_stats.put_requests == nparts  # one PUT per input partition

rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
s = rep.stats
# map downloads: every input byte re-read in store_chunk_bytes ranged GETs
part_bytes = 16 + N // nparts * plan.record_bytes
chunks_per_part = -(-part_bytes // plan.store_chunk_bytes)
# reduce fetches: <= one ranged GET per (wave, reducer) run slice
reduce_gets_max = rep.num_waves * rep.num_reducers
assert s.get_requests >= nparts * chunks_per_part
assert s.get_requests <= nparts * chunks_per_part + reduce_gets_max
# writes: one PUT per spilled run + >= one per multipart output part
assert s.put_requests >= rep.spill_objects + rep.output_objects
assert s.bytes_written >= 2 * N * plan.record_bytes  # spill + output legs
assert s.bytes_read >= 2 * N * plan.record_bytes     # map + reduce legs

# measured requests flow into the TCO (not the paper's 6M/1M constants)
from repro.core.cost_model import measured_cloudsort_tco
tco = measured_cloudsort_tco(s, job_hours=rep.job_hours,
                             reduce_hours=rep.reduce_hours,
                             data_bytes=N * plan.record_bytes)
from repro.core.cost_model import Ec2CostParams
p = Ec2CostParams()
assert tco.access_get == p.get_per_1000 * s.get_requests / 1000
assert tco.access_put == p.put_per_1000 * s.put_requests / 1000
print("OK")
""")


def test_single_round_and_wide_reducers():
    # num_rounds=1 degenerates to one-shot waves; R1=4 exercises ranged
    # reduce GETs over sub-worker slices.
    run_with_devices(SETUP.replace("num_rounds=2", "num_rounds=1")
                           .replace("reducers_per_worker=2",
                                    "reducers_per_worker=4") + """
rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
assert rep.output_objects == 8 * 4
val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert val.ok, val
print("OK")
""")


def test_throttled_tiered_store_completes_with_retries():
    # The acceptance run of ISSUE 2: latency + 503 throttling on the
    # durable tier, spill routed to the SSD tier, streaming reduce — and
    # the sort must still validate clean, with the absorbed faults visible
    # in the stats.
    run_with_devices("""
import tempfile
import jax
from repro.core.external_sort import ExternalSortPlan, external_sort
from repro.data import gensort, valsort
from repro.io.middleware import FaultProfile, RetryPolicy
from repro.io.tiered import tiered_cloudsort_store

from repro.core.compat import make_mesh
mesh = make_mesh((8,), ("w",))
plan = ExternalSortPlan(
    records_per_wave=1 << 13,
    num_rounds=2,
    reducers_per_worker=2,
    payload_words=2,
    impl="ref",
    input_records_per_partition=1 << 12,
    output_part_records=1 << 11,
    store_chunk_bytes=8 << 10,
    merge_chunk_bytes=4 << 10,
)
N = 1 << 15
store = tiered_cloudsort_store(
    tempfile.mkdtemp(prefix="extsort-faulty-"),
    spill_prefixes=(plan.spill_prefix,),
    faults=FaultProfile(latency_s=0.001, bandwidth_bps=400e6,
                        get_rate=60.0, put_rate=40.0, burst=8.0),
    retry=RetryPolicy(max_attempts=12, base_delay_s=0.01, max_delay_s=0.25),
)
store.create_bucket("sort")
in_ck, nparts = gensort.write_to_store(
    store, "sort", plan.input_prefix, N,
    plan.input_records_per_partition, plan.payload_words)

rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert val.ok and val.total_records == N, val

# faults were really injected and really absorbed: retries show in stats
s = rep.stats
assert s.retries > 0 and s.throttled > 0, s
# every throttle came from a (re-)issued attempt; >= covers the rare case
# where one op exhausts the store-level budget and staging re-reads it
assert s.throttled >= s.retries
assert s.stall_seconds > 0
# retry inflation: durable GET attempts > the billed-clean count would be
d = rep.tier_stats["durable"]
assert d.get_requests > nparts  # at least the map chunk GETs, inflated
assert d.retries == s.retries  # only the durable tier has a fault stack

# tier routing: all spill traffic on the SSD tier, none durable
ssd = rep.tier_stats["ssd"]
assert ssd.put_requests == rep.spill_objects
assert ssd.throttled == 0 and ssd.retries == 0
assert ssd.get_requests > 0  # streaming reduce fetches run chunks from ssd
assert d.bytes_written > 0   # output partitions land durable
print("OK", s.retries, "retries absorbed")
""", timeout=900)


def test_streaming_reduce_peak_memory_bounded_by_chunk_sweep():
    # Peak merge memory must scale with merge_chunk_bytes (runs x chunk),
    # not with partition size: sweep the chunk size on the same dataset.
    # parallel_reducers=1 isolates the per-merge contract; the global
    # budget governor has its own test below.
    run_with_devices(SETUP + """
import dataclasses
partition_bytes = N // (8 * plan.reducers_per_worker) * plan.record_bytes
peaks = {}
for chunk in (1 << 12, 1 << 14):
    p = dataclasses.replace(plan, merge_chunk_bytes=chunk, parallel_reducers=1)
    rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=p)
    val = valsort.validate_from_store(store, "sort", p.output_prefix, in_ck)
    assert val.ok, (chunk, val)
    assert rep.runs_per_reducer == rep.num_waves == 4
    # the contract: peak <= runs x chunk, and the bound is real (nonzero)
    assert 0 < rep.reduce_peak_merge_bytes <= rep.runs_per_reducer * chunk, rep
    peaks[chunk] = rep.reduce_peak_merge_bytes
# the bound binds: a smaller chunk budget means a smaller measured peak,
# and the small-chunk peak sits well under one output partition
assert peaks[1 << 12] < peaks[1 << 14]
assert peaks[1 << 12] < partition_bytes, (peaks, partition_bytes)
print("OK", peaks)
""")


def test_parallel_reduce_deterministic_and_budget_bounded():
    # The scheduler contract (ISSUE 3): parallel_reducers=4 must produce
    # output objects byte-identical (same CRC etag, size, part count) to
    # parallel_reducers=1, and the measured all-reducer peak merge memory
    # must respect the global reduce_memory_budget_bytes.
    run_with_devices(SETUP + """
import dataclasses
budget = 16 << 10  # < one output partition (32 KiB at these parameters)
partition_bytes = N // (8 * plan.reducers_per_worker) * plan.record_bytes
assert budget < partition_bytes
etags = {}
for par in (1, 4):
    p = dataclasses.replace(plan, parallel_reducers=par,
                            reduce_memory_budget_bytes=budget,
                            part_upload_fanout=1 if par == 1 else 3)
    rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=p)
    assert rep.parallel_reducers == par
    assert 0 < rep.reduce_peak_merge_bytes <= budget, rep
    assert rep.reduce_memory_bound_bytes == budget
    val = valsort.validate_from_store(store, "sort", p.output_prefix, in_ck)
    assert val.ok, (par, val)
    etags[par] = [(m.key, m.etag, m.size, m.parts)
                  for m in store.list_objects("sort", p.output_prefix)]
    assert len(etags[par]) == 16
# byte-identical partitions: same keys, same CRC etags, same part layout
assert etags[1] == etags[4], (etags[1], etags[4])
# the span timeline measured real overlapped reduce work
assert rep.phase_seconds.get("reduce.merge", 0) > 0
assert rep.phase_seconds.get("reduce.upload", 0) > 0
# the pipelined map records its device sort once, as map.device_sort
assert rep.phase_seconds.get("map.device_sort", 0) > 0
assert "map.compute" not in rep.phase_seconds
print("OK", etags[4][:2])
""")


def test_adaptive_governor_grows_chunks_and_never_exceeds_budget():
    # The ISSUE-4 governor contract: reducers retire at staggered times
    # (16 partitions on a width-3 scheduler — one straggler always runs
    # alone at the tail), freed budget is re-apportioned so live merges'
    # chunks GROW mid-merge, and the measured all-reducer peak still
    # never exceeds the global budget. Bytes must not change vs. the
    # uncapped run (chunking is invisible in the output).
    run_with_devices(SETUP + """
import dataclasses
rep0 = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
want = [(m.key, m.etag, m.size, m.parts)
        for m in store.list_objects("sort", plan.output_prefix)]

budget = 16 << 10
p = dataclasses.replace(plan, parallel_reducers=3,
                        reduce_memory_budget_bytes=budget,
                        merge_chunk_bytes=16 << 10)
rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=p)
val = valsort.validate_from_store(store, "sort", p.output_prefix, in_ck)
assert val.ok, val
got = [(m.key, m.etag, m.size, m.parts)
       for m in store.list_objects("sort", p.output_prefix)]
assert got == want, "budget governance changed output bytes"
# the hard bound: measured peak under the budget at every instant
assert 0 < rep.reduce_peak_merge_bytes <= budget, rep
assert rep.reduce_memory_bound_bytes == budget
# adaptivity observed: the governor granted a bigger chunk than the
# static split once siblings retired (static would pin chunk_bytes)
assert rep.reduce_chunk_bytes == (budget // 3) // rep.num_waves
assert rep.reduce_chunk_bytes_max > rep.reduce_chunk_bytes, rep
# and the growth is still capped by the plan's merge_chunk_bytes
assert rep.reduce_chunk_bytes_max <= p.merge_chunk_bytes
print("OK", rep.reduce_chunk_bytes, "->", rep.reduce_chunk_bytes_max)
""")


def test_validate_from_store_catches_corruption():
    run_with_devices(SETUP + """
rep = external_sort(store, "sort", mesh=mesh, axis_names="w", plan=plan)
# flip one payload word of one output partition, re-upload, re-validate
key = store.list_objects("sort", plan.output_prefix)[3].key
from repro.io import records as rec
k, i, p = rec.decode_records(store.get("sort", key))
p = p.copy(); p[7, 1] ^= 1
store.put("sort", key, rec.encode_records(k, i, p))
val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert not val.checksum_match and not val.ok
# and an ordering violation in a different partition is caught too
key2 = store.list_objects("sort", plan.output_prefix)[5].key
k, i, p = rec.decode_records(store.get("sort", key2))
k = k.copy(); k[0], k[-1] = k[-1], k[0]
store.put("sort", key2, rec.encode_records(k, i, p))
val = valsort.validate_from_store(store, "sort", plan.output_prefix, in_ck)
assert not val.sorted_within
print("OK")
""")
