"""Program spans as PhaseTimeline.span records them: where the map's and
the reduce's host time goes (nested stage spans, one span per reduce
partition), the thread's CPU seconds on every timed block, and the
profiler annotation each block leaves in a JAX trace.

The jobs run in-process on one CPU device, small enough for a test.
"""
from __future__ import annotations

import collections
import glob
import os

import pytest

from repro.obs.events import Tracer
from repro.shuffle.runtime import PhaseTimeline

#: Spans still recorded after the fact (PhaseTimeline.add), without cpu_s
#: or an annotation: the wait's task is known only when it ends.
POST_HOC = {"map.wait"}
EPS = 1e-9


def _sort_job(root: str):
    """A two-wave pipelined sort of 100-byte records; returns the plan,
    the report and the job tracer's events."""
    from repro.core.compat import make_mesh
    from repro.core.external_sort import ExternalSortPlan
    from repro.data import gensort
    from repro.io.object_store import ObjectStore
    from repro.shuffle.sort import sort_shuffle_job

    plan = ExternalSortPlan(
        records_per_wave=1 << 13, num_rounds=2, reducers_per_worker=4,
        payload_words=23, input_records_per_partition=1 << 12,
        output_part_records=1 << 10, store_chunk_bytes=16 << 10,
        parallel_reducers=2)
    store = ObjectStore(os.path.join(root, "store"))
    store.create_bucket("sort")
    gensort.write_to_store(store, "sort", plan.input_prefix, 1 << 14,
                           plan.input_records_per_partition,
                           plan.payload_words)
    tracer = Tracer(job="spans")
    mesh = make_mesh((1,), ("w",))
    rep = sort_shuffle_job(store, "sort", mesh=mesh, axis_names="w",
                           plan=plan, tracer=tracer).run(workers=0)
    return plan, rep, tracer.log.events()


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    return _sort_job(str(tmp_path_factory.mktemp("spans")))


def _named(events, *names):
    return [e for e in events if e["name"] in names]


def _inside(child, parents) -> bool:
    return any(p["task"] == child["task"] and p["t"] <= child["t"] + EPS
               and child["t"] + child["dur"] <= p["t"] + p["dur"] + EPS
               for p in parents)


def test_map_stage_spans_nest_in_their_stage(job):
    _, rep, events = job
    encode = _named(events, "map.encode")
    device = _named(events, "map.device_sort")
    gather, pack = _named(events, "map.gather"), _named(events, "map.pack")
    children = _named(events, "map.device_lock", "map.h2d", "map.sort",
                      "map.d2h")
    assert gather and pack and len(children) == 4 * len(device) == 8
    assert all(_inside(e, encode) for e in gather + pack)
    assert all(_inside(e, device) for e in children)
    ps = rep.phase_seconds
    assert ps["map.gather"] + ps["map.pack"] <= ps["map.encode"]
    assert (ps["map.device_lock"] + ps["map.h2d"] + ps["map.sort"]
            + ps["map.d2h"] <= ps["map.device_sort"])
    # map.encode leaves out each run's spill submit.
    assert not any(_inside(e, encode)
                   for e in _named(events, "map.spill_wait"))
    # The device sort is recorded once, not mirrored into map.compute.
    assert "map.compute" not in ps


def test_one_reduce_partition_span_per_partition(job):
    plan, rep, events = job
    parts = _named(events, "reduce.partition")
    assert sorted(e["task"] for e in parts) == sorted(
        f"r{r}" for r in range(rep.num_partitions))
    assert rep.num_partitions == plan.reducers_per_worker
    for name in ("reduce.fetch", "reduce.merge"):
        assert all(_inside(e, parts) for e in _named(events, name)), name


def test_every_timed_block_carries_its_cpu_seconds(job):
    _, _, events = job
    spans = [e for e in events if e["dur"] > 0]
    assert {e["name"] for e in spans if "cpu_s" not in e} <= POST_HOC
    timed = [e for e in spans if "cpu_s" in e]
    assert len(timed) > 50
    assert all(0 <= e["cpu_s"] <= e["dur"] + 1e-3 for e in timed)


def test_spans_appear_as_profiler_annotations(tmp_path):
    import jax
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        _, _, events = _sort_job(str(tmp_path))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    timed = collections.defaultdict(list)
    for e in events:
        if "cpu_s" in e:
            timed[e["name"]].append(e["dur"])
    host = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in timed:
                    host[ev.name].append((ev.end_ns - ev.start_ns) / 1e9)
    assert {"map.gather", "map.h2d", "reduce.partition"} <= set(timed)
    diffs = []
    for name, durs in timed.items():
        assert len(host[name]) == len(durs), name
        pairs = list(zip(sorted(host[name]), sorted(durs)))
        # The annotation opens before the span's clock reads and closes
        # after them, so it is never shorter.
        assert all(a >= b - 5e-5 for a, b in pairs), name
        diffs += [a - b for a, b in pairs]
    # It is longer by the reads, and now and then by a GIL hand-off to
    # another of the job's threads landing between them: most spans
    # agree within 1 ms.
    assert sum(d < 1e-3 for d in diffs) >= 0.75 * len(diffs)


def test_span_records_cpu_seconds_and_nothing_for_a_raising_block():
    seen = []
    timeline = PhaseTimeline(sink=lambda *a: seen.append(a))
    with timeline.span("stage", worker="w0/g1"):
        sum(range(10000))
    with pytest.raises(ValueError):
        with timeline.span("lost", worker="w0/g2"):
            raise ValueError
    timeline.add("waited", 1.0, 3.0, worker="r4", nbytes=7)
    assert [a[0] for a in seen] == ["stage", "waited"]
    phase, start, end, tag, attrs = seen[0]
    assert tag == "w0/g1" and 0 <= attrs["cpu_s"] <= end - start + 1e-3
    assert seen[1][1:] == (1.0, 3.0, "r4", {"nbytes": 7})
    assert set(timeline.totals()) == {"stage", "waited"}


def test_tracer_sink_carries_span_attributes():
    tracer = Tracer(job="attrs")
    timeline = PhaseTimeline(sink=tracer.timeline_sink())
    with timeline.span("reduce.merge", worker="w1/r3"):
        pass
    (ev,) = tracer.log.events()
    assert (ev["name"], ev["worker"], ev["task"]) == ("reduce.merge", "w1",
                                                      "r3")
    assert ev["cpu_s"] >= 0
