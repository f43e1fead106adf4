"""CPU tests of the benchmark's parts: the command's refusal off the chip,
lookup by name, the generator, the metric arithmetic, the tables and the
trace reduction. None of them loads the TPU library."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

import chipbench_tiny as tiny
from harness import gen, reference, tables
from harness import trace as tr
from harness.registry import Bench

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_command_refuses_cpu_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "indy-uniform",
         "--seed", "7", "--seconds", "1", "--trace", "0"],
        cwd=tiny.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "not tpu" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_cell_config_traffic_metric_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (root / sub).mkdir(parents=True)
    (root / "configs" / "new_cfg.json").write_text('{"waves": 3}')
    (root / "traffic" / "new_mix.json").write_text('{"keys": "hot"}')
    (root / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run.answer\n")
    spec = {
        "workloads": [{"name": "new-cell", "config": "new_cfg",
                       "traffic": "new_mix", "chips": 1, "why": "x"}],
        "end_to_end": [{"name": "e2e", "unit": "s", "better": "lower",
                        "bound": 0.1, "source": "host_clock"}],
        "per_layer": [{"name": "new_metric", "unit": "s", "better": "lower",
                       "source": "program_span", "layer": "l",
                       "moves": "e2e"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = Bench.load(str(tmp_path / "BENCHMARK.json"), root=str(root))
    cell = bench.cell("new-cell")
    assert bench.config(cell["config"]) == {"waves": 3}
    assert bench.traffic(cell["traffic"]) == {"keys": "hot"}
    assert [m["name"] for m in bench.metrics("new-cell", True)] == [
        "new_metric"]
    assert bench.reader("new_metric")(types.SimpleNamespace(answer=4)) == 4
    with pytest.raises(LookupError):
        bench.cell("absent")


def test_metric_selection_follows_workloads_keys():
    bench = Bench(spec=tiny.spec(), root=tiny.BENCH_DIR)
    assert [m["name"] for m in bench.metrics("indy-uniform", True)] == [
        m["name"] for m in tiny.spec()["per_layer"]]
    assert {m["name"] for m in bench.metrics("indy-uniform", False)} == {
        "sort_MBps", "usd_per_TB", "setup_s"}
    # A metric whose workloads list names other cells is not read here.
    spec = dict(tiny.spec(), per_layer=[
        dict(m, workloads=["other-cell"]) for m in tiny.spec()["per_layer"]])
    assert Bench(spec=spec, root=tiny.BENCH_DIR).metrics(
        "indy-uniform", True) == []


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_seed_layout_contiguous_and_unwrapped(seed):
    total = 1 << 25
    first = gen.first_id(seed, total)
    assert 0 <= first and first + total <= 1 << 32
    assert gen.first_id(seed, total) == first


def test_traffic_with_unknown_keys_is_refused():
    with pytest.raises(ValueError, match="keys="):
        gen.make_input(total_records=1 << 10, records_per_object=1 << 10,
                       payload_words=23, variant="zipf", seed=1,
                       prefix="input/")


def test_generator_deterministic_and_matches_gensort():
    from repro.data import gensort

    make = lambda seed: gen.make_input(  # noqa: E731
        total_records=1 << 12, records_per_object=1 << 10, payload_words=23,
        variant="uniform", seed=seed, prefix="input/")
    a, b, c = make(2**31 + 5), make(2**31 + 5), make(9)
    rows = a.all_rows()
    assert np.array_equal(rows, b.all_rows()) and a.checksum == b.checksum
    assert [d for _, d in a.objects] == [d for _, d in b.objects]
    assert not np.array_equal(rows, c.all_rows())
    ids = rows[:, 1].astype(np.int64)
    assert np.array_equal(ids, a.first_id + np.arange(ids.size))
    # The copy agrees with the program's gensort record for record.
    import jax.numpy as jnp

    jids = jnp.asarray(rows[:, 1])
    assert np.array_equal(rows[:, 0], np.asarray(gensort.splitmix32(jids)))
    assert np.array_equal(rows[:, 2:], np.asarray(gensort.gen_payload(jids)))
    s, x = gensort.checksum(jnp.asarray(rows[:, 0]), jids,
                            jnp.asarray(rows[:, 2:]))
    assert a.checksum == (int(s), int(x))
    assert np.array_equal(a.rows(1), rows[1 << 10:2 << 10])


def _run(**kw):
    base = dict(jobs=[], chips=1, durable_gets=0, durable_puts=0,
                partition_records=[], prices=None, device_trace=None)
    base.update(kw)
    return types.SimpleNamespace(**base)


def _reader(name):
    return Bench(spec=tiny.spec(), root=tiny.BENCH_DIR).reader(name)


def test_usd_per_tb_arithmetic():
    prices = {"chip_hour": 1.20, "get_per_1000": 0.0004,
              "put_per_1000": 0.005}
    # 1 TB sorted in 3600 s on one chip with 1e6 GETs and 1e5 PUTs:
    # $1.20 + $0.40 + $0.50.
    run = _run(prices=prices, window_s=3600.0, input_bytes=10**12,
               durable_gets=10**6, durable_puts=10**5)
    assert _reader("usd_per_TB")(run) == pytest.approx(2.10)
    run.chips, run.input_bytes = 4, 2 * 10**12
    assert _reader("usd_per_TB")(run) == pytest.approx((4.80 + 0.9) / 2)
    assert _reader("usd_per_TB")(_run(window_s=1.0, input_bytes=1)) is None


def test_partition_imbalance_arithmetic():
    read = _reader("partition_imbalance")
    assert read(_run(partition_records=[10, 10, 10, 10])) == 1.0
    assert read(_run(partition_records=[40, 10, 20, 10])) == 2.0
    assert read(_run(partition_records=[])) is None


def test_sort_rate_and_idle_arithmetic():
    run = _run(window_s=2.0, input_bytes=10**9)
    assert _reader("sort_MBps")(run) == 500.0
    run.device_trace = {"busy_s": 0.5, "window_s": 2.0}
    assert _reader("device_idle")(run) == 75.0
    run.device_trace = {"busy_s": 0.0, "window_s": 2.0}
    assert _reader("device_idle")(run) is None


def test_price_table_refuses_unknown_device():
    assert tables.prices("TPU v5 lite")["chip_hour"] == 1.20
    with pytest.raises(tables.UnknownDevice):
        tables.prices("TPU v99")


def test_trace_reduction_on_synthetic_trace():
    ms = 1e6
    ops = [("sort", 10 * ms, 20 * ms), ("fusion", 15 * ms, 25 * ms),
           ("sort", 60 * ms, 70 * ms), ("copy", 95 * ms, 120 * ms)]
    spans = [("map.decode", 0, 40 * ms), ("map.encode", 30 * ms, 50 * ms),
             ("reduce.merge", 70 * ms, 90 * ms)]
    out = tr.reduce_trace(ops, spans, (0.0, 100 * ms))
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["device_ops"] == [["sort", pytest.approx(0.020)],
                                 ["fusion", pytest.approx(0.010)],
                                 ["copy", pytest.approx(0.005)]]
    # Idle: [0,10) decode; [25,30) decode; [30,50) encode (newest open
    # span); [50,60) none; [70,90) merge; [90,95) none.
    assert out["idle_gaps"] == [
        ["map.encode", pytest.approx(0.020)],
        ["reduce.merge", pytest.approx(0.020)],
        ["map.decode", pytest.approx(0.010)],
        ["none", pytest.approx(0.010)],
        ["map.decode", pytest.approx(0.005)],
        ["none", pytest.approx(0.005)]]


def test_trace_reduction_on_recorded_chip_trace():
    path = os.path.join(DATA, "v5e_sort.xplane.pb")
    with open(os.path.join(DATA, "v5e_sort.expected.json")) as f:
        want = json.load(f)
    ops, mark = tr.load_xplane(path, "chipbench.clock")
    assert mark == want["marker_ns"] and len(ops) == want["ops"]
    lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    out = tr.reduce_trace(ops, [("host.wait", lo, hi)], (lo, hi))
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["top_op"]
    assert 0 < out["busy_s"] <= out["window_s"]


def test_compare_counts_each_fault_of_the_output():
    inp = gen.make_input(total_records=1 << 12, records_per_object=1 << 10,
                         payload_words=23, variant="uniform", seed=3,
                         prefix="input/")
    ref = reference.reference_order(inp)
    ids = (ref & np.uint64(0xFFFFFFFF)).astype(np.int64) - inp.first_id
    rows = inp.all_rows()[ids]
    good = [gen.header(n.shape[0], 23) + n.tobytes()
            for n in np.array_split(rows, 4)]
    assert reference.passes(reference.compare(inp, good))
    flipped = rows.copy()
    flipped[5, 7] ^= 1
    nums = reference.compare(inp, [gen.header(4096, 23) + flipped.tobytes()])
    assert nums["records_differing"] == 1 and nums["checksum_differs"] == 1
    nums = reference.compare(inp, [gen.header(2048, 23)
                                   + rows[:2048].tobytes()])
    assert nums["records_missing"] == 2048
    swapped = rows[::-1].copy()
    nums = reference.compare(inp, [gen.header(4096, 23) + swapped.tobytes()])
    assert nums["order_breaks"] > 0 and nums["checksum_differs"] == 0


def test_benchmark_json_shape():
    spec = tiny.spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [e["name"] for e in spec["configs"] + spec["workloads"] + metrics]
    assert all(name.match(n) for n in names) and len(set(names)) == len(names)
    for entry in spec["configs"]:
        assert os.path.isfile(os.path.join(tiny.CHECKOUT, entry["file"]))
        with open(os.path.join(tiny.CHECKOUT, entry["file"])) as f:
            config = json.load(f)
        assert set(entry["reduced"]) <= set(config)
        assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for cell in spec["workloads"]:
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    for m in metrics:
        assert os.path.isfile(os.path.join(tiny.BENCH_DIR, "metrics",
                                           f"{m['name']}.py"))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in spec["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in spec["end_to_end"])
