"""Out-of-core external sort through the object store (paper §2.3–§2.5).

Tracks, from this PR onward: end-to-end sorted records/s at a fixed
out-of-core oversubscription, the measured GET/PUT request counts (the
Table-2 access legs), the measured-TCO total for the run, and the
per-phase span timeline (map wait/device sort/spill, reduce
fetch/merge/upload) so stage overlap is a number, not a narrative. Runs
on however many devices the harness process has (typically 1) — the
point is the store path, not the collective.

Standalone: PYTHONPATH=src python benchmarks/bench_external_sort.py [--smoke|--full]
`run()` (the benchmarks/run.py entry) always uses smoke scale, parity
with bench_store_faults; --full sorts 4x the records.
"""
from __future__ import annotations

import tempfile
import time

#: Regression gates for tools/bench_diff.py: only machine-independent
#: rows are gated (request counts are exact functions of the plan, not
#: of runner speed); timings — and the measured TCO, whose compute-VM
#: leg is priced off wall-clock runtime — stay informational because CI
#: runners are noisy.
GATES = {
    "extsort_get_requests": {"tolerance": 0.25, "direction": "lower"},
    "extsort_put_requests": {"tolerance": 0.25, "direction": "lower"},
}


def run(full: bool = False):
    import jax

    from repro.core.cost_model import measured_cloudsort_tco
    from repro.core.external_sort import ExternalSortPlan, external_sort
    from repro.data import gensort, valsort
    from repro.io.object_store import ObjectStore

    w = len(jax.devices())
    from repro.core.compat import make_mesh
    mesh = make_mesh((w,), ("w",))
    plan = ExternalSortPlan(
        records_per_wave=(1 << (13 if full else 12)) * w,
        num_rounds=2,
        reducers_per_worker=4,
        payload_words=4,
        impl="ref",
        input_records_per_partition=(1 << (12 if full else 11)) * w,
        output_part_records=1 << 12,
        store_chunk_bytes=32 << 10,
    )
    total = plan.records_per_wave * 4  # 4x out-of-core
    root = tempfile.mkdtemp(prefix="bench-extsort-")
    store = ObjectStore(root)
    store.create_bucket("bench")

    in_ck, _ = gensort.write_to_store(
        store, "bench", plan.input_prefix, total,
        plan.input_records_per_partition, plan.payload_words)

    t0 = time.perf_counter()
    rep = external_sort(store, "bench", mesh=mesh, axis_names="w", plan=plan)
    wall = time.perf_counter() - t0
    val = valsort.validate_from_store(store, "bench", plan.output_prefix, in_ck)
    assert val.ok, val

    tco = measured_cloudsort_tco(
        rep.stats, job_hours=rep.job_hours, reduce_hours=rep.reduce_hours,
        data_bytes=total * plan.record_bytes)
    us = wall * 1e6
    rows = [
        ("extsort_total", us, total / wall),  # derived: records/s
        ("extsort_map", rep.map_seconds * 1e6, rep.oversubscription),
        ("extsort_reduce", rep.reduce_seconds * 1e6, rep.num_reducers),
        ("extsort_get_requests", us, rep.stats.get_requests),
        ("extsort_put_requests", us, rep.stats.put_requests),
        # streaming-reduce working set: measured peak vs the global bound
        ("extsort_reduce_peak_bytes", rep.reduce_seconds * 1e6,
         rep.reduce_peak_merge_bytes),
        ("extsort_measured_tco_usd", us, tco.total),
    ]
    # Span timeline: us = summed span seconds of the phase; derived = that
    # work as a fraction of its stage's wall time (>1 means the phase ran
    # overlapped across threads — the §2.5 claim, measured).
    ph = rep.phase_seconds
    stage_wall = {"map": rep.map_seconds, "reduce": rep.reduce_seconds}
    for phase in ("map.wait", "map.device_sort", "map.spill",
                  "reduce.fetch", "reduce.merge", "reduce.upload"):
        secs = ph.get(phase, 0.0)
        denom = stage_wall[phase.split(".", 1)[0]]
        rows.append((f"extsort_span_{phase.replace('.', '_')}",
                     secs * 1e6, secs / denom if denom > 0 else 0.0))
    return rows


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--smoke", action="store_true",
                      help="small dataset (the default)")
    mode.add_argument("--full", action="store_true",
                      help="4x the records per wave and per partition")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for name, us, derived in run(full=args.full):
        print(f"{name},{us:.3f},{derived:.6g}")


if __name__ == "__main__":
    main()
