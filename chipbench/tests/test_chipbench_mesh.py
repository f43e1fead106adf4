"""The four-worker deployment (`indy-uniform-4chip`) on the CPU at a tiny
size, and the readers of the mesh exchange.

One child process with four host devices runs, in turn: a wave's transfer to
the mesh, watched at the sort's door; a traced run of the cell through the
harness; the same job on one worker and on four, against the reference; and
the cell again with one destination's block dropped from every all-to-all,
which has to make `correct` false. The readers are checked on hand-made
per-chip ops and span events, and on a trace recorded on a v5e 2x2
(data/v5e_2x2_exchange.xplane.pb: the four chips of two wave sorts of the
cell, 2^24 records each, trimmed to their all-to-alls and the first 12
other ops of each chip).
"""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

import chipbench_tiny as tiny
from harness import exchange, tables
from harness import trace as tr
from harness.registry import Bench
from harness.runner import Job, Run

sys.path.insert(0, os.path.join(tiny.CHECKOUT, "tests"))
from helpers import run_with_devices  # noqa: E402

SEED = 2**31 + 203
CELL = "indy-uniform-4chip"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

CHILD = """
import json, os, pathlib, sys, time
sys.path.insert(0, {tests!r})
import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from harness import gen, reference, runner
from harness.runner import Options, run_cell

runner.GRACE_S = 3.0
tmp = pathlib.Path({tmp!r})
devices = jax.devices()
bench = tiny.tiny_bench(str(tmp / "bench"))
out = {{"devices": len(devices)}}


def cell_run(name, trace):
    t = time.perf_counter()
    return run_cell(bench, name, devices, None, Options(
        seed={seed}, seconds=0.5, trace=trace, t_start=t, t_import=t,
        workdir=str(tmp / ("work-" + name))))


from repro.core.compat import make_mesh
from repro.core.external_sort import WaveSorter
from repro.shuffle.recursive import recursive_sort

config = bench.config(bench.cell({cell!r})["config"])
plan = runner.plan_from(config)
inp = gen.make_input(
    total_records=plan.records_per_wave * config["waves"],
    records_per_object=plan.input_records_per_partition,
    payload_words=plan.payload_words, variant="uniform", seed={seed},
    prefix=plan.input_prefix)

# Where the sort finds each mesh worker's keys and ids.
sorter = WaveSorter(plan, make_mesh((4,), ("w",), devices=devices), "w")
sort, placed = sorter._sort, []


def watched(dk, di):
    placed.extend(sorted([s.device.id, s.data.shape[0]]
                         for s in x.addressable_shards) for x in (dk, di))
    return sort(dk, di)


sorter._sort = watched
rows = inp.all_rows()[:plan.records_per_wave]
sorter.device_sort(rows[:, 0], rows[:, 1])
out["placed"] = placed

out["traced"] = cell_run({cell!r}, True)


def job_rows(width):
    store = runner.build_store(str(tmp / f"spill-{{width}}"), plan)
    store.create_bucket(runner.BUCKET)
    for key, data in inp.objects:
        store.put(runner.BUCKET, key, data)
    recursive_sort(store, runner.BUCKET,
                   mesh=make_mesh((width,), ("w",), devices=devices[:width]),
                   axis_names="w", plan=plan, workers=0)
    metas = store.list_objects(runner.BUCKET, plan.output_prefix)
    return len(metas), np.concatenate([
        gen.object_rows(store.get(runner.BUCKET, m.key), plan.payload_words)
        for m in metas])


(parts4, rows4), (parts1, rows1) = job_rows(4), job_rows(1)
ref = reference.reference_order(inp)
k64 = (rows4[:, 0].astype(np.uint64) << np.uint64(32)) | rows4[:, 1]
out["jobs"] = {{"parts": [parts4, parts1],
               "equal_to_one_worker": bool(np.array_equal(rows4, rows1)),
               "equal_to_reference": bool(np.array_equal(k64, ref))}}

# The fault: one destination's block dropped from every all-to-all.
from repro.core import sortlib

partition_sorted = sortlib.partition_sorted


def dropped(sorted_keys, boundaries, **kw):
    starts, counts = partition_sorted(sorted_keys, boundaries, **kw)
    return starts, counts.at[..., 1].set(0)


pytest.MonkeyPatch().setattr(sortlib, "partition_sorted", dropped)
out["dropped"] = cell_run({cell!r}, False)
print(json.dumps(out), flush=True)
os._exit(0)
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh")
    code = CHILD.format(tests=os.path.dirname(os.path.abspath(__file__)),
                        tmp=str(tmp), seed=SEED, cell=CELL)
    stdout = run_with_devices(code, n_devices=4, timeout=600)
    return json.loads(stdout.strip().splitlines()[-1])


def test_h2d_places_each_shard_on_its_own_device(child):
    n = tiny.TINY["records_per_wave"] // 4
    # keys, then ids: one shard of n records on each of the four devices.
    assert child["placed"] == [[[d, n] for d in range(4)]] * 2


def test_four_chip_cell_is_correct_and_reads_the_exchange(child):
    result = child["traced"]
    assert child["devices"] == 4
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert all(v["value"] == 0 for v in result["checks"].values())
    assert len(result["checks"]) == 6
    # 2^13 records a wave, 2 rounds of 2^10 on each of 4 workers: a mean
    # block of 256 records, capacity 384 rounded up to 512 slots.
    assert result["metrics"]["exchange_padding_share"]["value"] == 50.0
    # A CPU trace has no TPU plane, so neither device-trace metric is read.
    assert "exchange_s_per_GB" not in result["metrics"]
    assert "exchange_ici_roofline" not in result["metrics"]


def test_four_workers_write_the_one_worker_records(child):
    jobs = child["jobs"]
    assert jobs["parts"] == [64, 16]
    assert jobs["equal_to_one_worker"] and jobs["equal_to_reference"]


def test_dropped_exchange_block_makes_run_incorrect(child):
    result = child["dropped"]
    assert not result["correct"] and result["failed"] >= 1
    assert result["checks"]["records_missing"]["value"] > 0


def test_tiny_waves_divide_over_workers_and_rounds():
    for entry in tiny.spec()["configs"]:
        with open(os.path.join(tiny.CHECKOUT, entry["file"])) as f:
            config = json.load(f)
        share = config["mesh_workers"] * config["num_rounds"]
        assert tiny.TINY["records_per_wave"] % share == 0
        assert config["records_per_wave"] % share == 0


def test_four_chip_cell_reads_only_exchange_metrics():
    bench = Bench(spec=tiny.spec(), root=tiny.BENCH_DIR)
    assert bench.config(bench.cell(CELL)["config"])["mesh_workers"] == 4
    assert [m["name"] for m in bench.metrics(CELL, True)] == [
        "exchange_s_per_GB", "exchange_ici_roofline",
        "exchange_padding_share"]
    assert {m["name"] for m in bench.metrics(CELL, False)} == {
        "sort_MBps", "usd_per_TB", "setup_s"}


def _op(name, opcode, s, e, operand="%x"):
    return (f"%{name} = u32[4,1,4096]{{2,1,0:T(1,128)S(1)}} "
            f"{opcode}({operand}), channel_id=1", float(s), float(e))


#: Four chips, uneven: chip 1 exchanges longest, chip 3 ran one op alone.
PLANES = {
    "/device:TPU:0": [_op("all-to-all-start.1", "all-to-all-start", 0, 1),
                      _op("fusion.2", "fusion", 1, 5, "%all-to-all-start.1"),
                      _op("all-to-all-done.1", "all-to-all-done", 6, 7),
                      _op("all_to_all.28", "all-to-all", 10, 12)],
    "/device:TPU:1": [_op("all-to-all-start.1", "all-to-all-start", 0, 1),
                      _op("all-to-all-start.2", "all-to-all-start", 2, 3),
                      _op("all-to-all-done.1", "all-to-all-done", 8, 9),
                      _op("all-to-all-done.2", "all-to-all-done", 11, 12)],
    "/device:TPU:2": [_op("all-to-all-start", "async-start", 0, 1),
                      _op("all-to-all-done", "async-done", 3, 4),
                      _op("sort.6", "sort", 4, 9, "%all_to_all.28")],
    "/device:TPU:3": [_op("all_to_all.29", "all-to-all", 5, 6)],
}


def test_all_to_all_seconds_per_chip():
    assert [exchange.all_to_all_intervals(PLANES["/device:TPU:1"])] == [
        [(0.0, 9.0), (2.0, 12.0)]]
    # Nanoseconds: chip 0 holds a pair (0-7) and an op (10-12), chip 1
    # two overlapping pairs (0-12), chip 2 an async pair (0-4).
    assert exchange.chip_seconds(PLANES) == pytest.approx({
        "/device:TPU:0": 9e-9, "/device:TPU:1": 12e-9,
        "/device:TPU:2": 4e-9, "/device:TPU:3": 1e-9})


def _run(events=(), device_trace=None, input_bytes=10**9, workers=4):
    jobs = [Job(start=0.0, end=1.0, origin=0.0, events=list(evs), gauges={})
            for evs in events] or [Job(0.0, 1.0, 0.0, [], {})]
    return Run(config={"mesh_workers": workers}, chips=workers, jobs=jobs,
               records_per_job=input_bytes // (100 * len(jobs)),
               record_bytes=100, durable_gets=0, durable_puts=0,
               partition_records=[], setup_s=0.0, prices=None,
               device_trace=device_trace)


def _reader(name):
    return Bench(spec=tiny.spec(), root=tiny.BENCH_DIR).reader(name)


def test_exchange_seconds_per_gb_from_the_busiest_chip(tmp_path,
                                                       monkeypatch):
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(exchange, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(exchange, "load_planes", lambda path: PLANES)
    run = _run(device_trace={"busy_s": 1.0}, input_bytes=2 * 10**9)
    assert _reader("exchange_s_per_GB")(run) == pytest.approx(6e-9)


def test_one_chip_trace_reads_no_exchange(tmp_path, monkeypatch):
    # A recorded one-chip trace of a plain sort: its chip ran no all-to-all.
    planes = exchange.load_planes(os.path.join(DATA, "v5e_sort.xplane.pb"))
    assert list(planes) == ["/device:TPU:0"] and len(planes[
        "/device:TPU:0"]) == 15
    assert exchange.chip_seconds(planes) == {"/device:TPU:0": 0.0}
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(exchange, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(exchange, "load_planes", lambda path: planes)
    run = _run(device_trace={}, workers=1)
    assert _reader("exchange_s_per_GB")(run) == 0.0
    # No byte crosses between chips: the roofline reads 0, peak unasked.
    monkeypatch.setattr(exchange, "device_kind", lambda: "cpu")
    assert _reader("exchange_ici_roofline")(run) == 0.0


def test_useful_bytes_per_chip_by_hand():
    # 2^25 records over 4 chips: 2^23 on each, of which 3/4 leave it,
    # 8 bytes each (key and id).
    assert exchange.useful_bytes_per_chip(1 << 25, 4) == (1 << 23) * 3 // 4 * 8
    assert exchange.useful_bytes_per_chip(1 << 25, 4) == 50331648
    assert exchange.useful_bytes_per_chip(1000, 1) == 0


def test_ici_peak_table_refuses_unknown_device():
    # v5e: 1,600 Gbit/s per chip.
    assert exchange.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(tables.UnknownDevice):
        exchange.ici_bytes_per_s("TPU v99")


def test_roofline_takes_the_chip_with_the_lowest_share(tmp_path,
                                                       monkeypatch):
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(exchange, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(exchange, "load_planes", lambda path: PLANES)
    monkeypatch.setattr(exchange, "device_kind", lambda: "TPU v5 lite")
    # 1600 records: 300 useful x 8 B = 2400 B per chip. Chip 1 exchanged
    # longest, 12 ns, against the 200e9 B/s peak: 2400 / 2400 = 100%.
    run = _run(device_trace={"busy_s": 1.0}, input_bytes=1600 * 100)
    assert _reader("exchange_ici_roofline")(run) == pytest.approx(100.0)
    # A mesh wider than the chips that exchanged reads nothing.
    run = _run(device_trace={"busy_s": 1.0}, input_bytes=1600 * 100,
               workers=8)
    assert _reader("exchange_ici_roofline")(run) is None


def test_exchange_read_from_a_recorded_v5e_2x2_trace(tmp_path, monkeypatch):
    planes = exchange.load_planes(os.path.join(DATA,
                                               "v5e_2x2_exchange.xplane.pb"))
    assert sorted(planes) == [f"/device:TPU:{i}" for i in range(4)]
    # Each chip: 2 sorts x 2 rounds x 3 all-to-alls (keys, ids, counts),
    # each a single op, not an async pair.
    assert all(len(exchange.all_to_all_intervals(ops)) == 12
               for ops in planes.values())
    seconds = exchange.chip_seconds(planes)
    assert seconds == pytest.approx({
        "/device:TPU:0": 0.001877882, "/device:TPU:1": 0.001763079,
        "/device:TPU:2": 0.002180638, "/device:TPU:3": 0.00200954})
    monkeypatch.setattr(exchange, "TRACE_DIR", str(DATA))
    monkeypatch.setattr(tr, "find_xplane", lambda d: os.path.join(
        d, "v5e_2x2_exchange.xplane.pb"))
    monkeypatch.setattr(exchange, "device_kind", lambda: "TPU v5 lite")
    run = _run(device_trace={}, input_bytes=(1 << 25) * 100)
    assert _reader("exchange_s_per_GB")(run) == pytest.approx(
        0.002180638 / 3.3554432)
    # 50,331,648 useful bytes a chip over chip 2's 2.18 ms at 200 GB/s.
    assert _reader("exchange_ici_roofline")(run) == pytest.approx(
        100 * 50331648 / (0.002180638 * 200e9))


def _sort_ev(slots, records):
    return {"name": "map.sort", "t": 0.0, "dur": 1.0, "phase": "map",
            "task": None, "worker": "host", "mesh_workers": 4,
            "exchange_slots": slots, "exchange_records": records}


def test_padding_share_sums_the_window_sorts():
    run = _run([[_sort_ev(1 << 25, 1 << 24), _sort_ev(1 << 25, 1 << 24)],
                [_sort_ev(1 << 25, 3 << 23)]])
    # 3 x 2^25 slots carried 2^24 + 2^24 + 3 x 2^23 records.
    assert _reader("exchange_padding_share")(run) == pytest.approx(
        100.0 * (3 * 4 - 2 * 2 - 3) / 12)


@pytest.mark.parametrize("name", ["exchange_s_per_GB",
                                  "exchange_ici_roofline",
                                  "exchange_padding_share"])
def test_exchange_metric_is_silent_without_its_inputs(name, tmp_path,
                                                      monkeypatch):
    # An untraced run of a program whose map.sort carries no counts, and a
    # traced one whose trace is not where a run keeps it.
    monkeypatch.setattr(exchange, "TRACE_DIR", str(tmp_path / "none"))
    plain = [{"name": "map.sort", "t": 0.0, "dur": 1.0, "phase": "map",
              "task": None, "worker": "host"}]
    assert _reader(name)(_run([plain])) is None
    assert _reader(name)(_run([plain], device_trace={"busy_s": 1.0})) is None
    assert _reader(name)(types.SimpleNamespace(
        jobs=[], device_trace=None)) is None
