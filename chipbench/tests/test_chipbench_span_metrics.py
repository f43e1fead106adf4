"""The per-layer metrics read from the program's stage spans: their
arithmetic on hand-made events, their silence where a program records no
such span, and their values in a whole traced run on the CPU at a tiny
size."""
from __future__ import annotations

import pytest

import chipbench_tiny as tiny
import test_chipbench_runs as runs
from harness.registry import Bench
from harness.runner import Job, Run

NEW = ("map_gather_s_per_GB", "map_pack_s_per_GB", "map_host_offcpu_share",
       "map_transfer_s_per_GB", "reduce_merge_offcpu_share",
       "reduce_partition_time_imbalance")


def _reader(name):
    return Bench(spec=tiny.spec(), root=tiny.BENCH_DIR).reader(name)


def _ev(name, t, dur, **attrs):
    return {"name": name, "t": t, "dur": dur, "phase": name.split(".")[0],
            "task": None, "worker": "host", **attrs}


def _run(*job_events, input_bytes=10**9):
    jobs = [Job(start=0.0, end=1.0, origin=0.0, events=list(evs), gauges={})
            for evs in job_events]
    return Run(config={}, chips=1, jobs=jobs,
               records_per_job=input_bytes // (100 * len(jobs)),
               record_bytes=100, durable_gets=0, durable_puts=0,
               partition_records=[], setup_s=0.0, prices=None,
               device_trace=None)


def test_stage_span_seconds_per_gb():
    # Two jobs of 0.5 GB each: span seconds add over jobs, per 1 GB.
    run = _run([_ev("map.gather", 0, 1.5), _ev("map.pack", 2, 0.25),
                _ev("map.h2d", 3, 0.5), _ev("map.encode", 0, 4.0)],
               [_ev("map.gather", 0, 0.5), _ev("map.d2h", 1, 0.25)])
    assert _reader("map_gather_s_per_GB")(run) == pytest.approx(2.0)
    assert _reader("map_pack_s_per_GB")(run) == pytest.approx(0.25)
    assert _reader("map_transfer_s_per_GB")(run) == pytest.approx(0.75)


def test_offcpu_shares_over_spans_with_cpu_seconds():
    run = _run([_ev("map.decode", 0, 2.0, cpu_s=1.5),
                _ev("map.encode", 1, 2.0, cpu_s=0.5),
                _ev("map.encode", 4, 9.0),  # recorded after the fact
                _ev("map.gather", 1, 1.0, cpu_s=0.0),  # nested, not read
                _ev("reduce.merge", 5, 4.0, cpu_s=3.0)])
    assert _reader("map_host_offcpu_share")(run) == pytest.approx(50.0)
    assert _reader("reduce_merge_offcpu_share")(run) == pytest.approx(25.0)


def test_partition_time_imbalance_averages_jobs():
    parts = lambda *durs: [_ev("reduce.partition", i, d)  # noqa: E731
                           for i, d in enumerate(durs)]
    # max / median: 4 / 2 in the first job, 3 / 3 in the second.
    run = _run(parts(1.0, 2.0, 4.0), parts(3.0, 3.0))
    assert _reader("reduce_partition_time_imbalance")(run) == pytest.approx(
        1.5)


@pytest.mark.parametrize("name", NEW)
def test_metric_is_silent_without_its_spans(name):
    # The spans of a program that records none of the stage spans, nor
    # the CPU seconds: the reader reports nothing and does not raise.
    run = _run([_ev("map.decode", 0, 2.0), _ev("map.encode", 1, 2.0),
                _ev("map.device_sort", 2, 1.0), _ev("reduce.merge", 3, 1.0)])
    assert _reader(name)(run) is None


def test_traced_run_reads_span_metrics(tmp_path):
    result = runs._run(tmp_path, trace=True)
    assert result["correct"]
    metrics = result["metrics"]
    assert set(NEW) <= set(metrics)
    per_gb = {k: metrics[k]["value"] for k in metrics}
    assert per_gb["map_gather_s_per_GB"] + per_gb["map_pack_s_per_GB"] <= (
        per_gb["map_host_s_per_GB"])
    assert per_gb["map_transfer_s_per_GB"] <= per_gb[
        "map_device_sort_s_per_GB"]
    assert per_gb["reduce_partition_time_imbalance"] >= 1.0
    for share in ("map_host_offcpu_share", "reduce_merge_offcpu_share"):
        assert -1.0 <= per_gb[share] <= 100.0
