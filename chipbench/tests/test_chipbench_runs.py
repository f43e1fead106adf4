"""Whole runs of the harness on the CPU at a tiny size, past its look for a
chip: a sound run is correct, and the control and each fault that a one-chip
sort cell can have make `correct` come out false.

The faults are planted in the program underneath the timed path:
  - a sort step that returns its input unchanged;
  - half of each wave left out of the spilled runs;
  - one output record altered where the reduce produces it.
(A one-chip cell has no exchange between chips to leave out.)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import chipbench_tiny as tiny
from harness import gen, reference
from harness.runner import Options, run_cell

SEED = 2**31 + 101


def _run(tmp_path, cell="indy-uniform", trace=False):
    import jax

    bench = tiny.tiny_bench(str(tmp_path / "bench"))
    t = time.perf_counter()
    return run_cell(bench, cell, jax.devices()[:1], None, Options(
        seed=SEED, seconds=0.5, trace=trace, t_start=t, t_import=t,
        workdir=str(tmp_path / "work")))


def test_sound_run_is_correct(tmp_path):
    result = _run(tmp_path)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert all(v["value"] == 0 for v in result["checks"].values())
    assert {"sort_MBps", "setup_s"} <= set(result["metrics"])
    # Set-up in parts, its compiles apart; the parts stay inside setup_s.
    setup = result["setup"]
    assert {"device_init_s", "input_gen_s", "warm_s", "xla_compiles",
            "cache_loads", "programs_s"} <= set(setup)
    assert setup["xla_compiles"] + setup["cache_loads"] >= 1
    assert (setup["input_gen_s"] + setup["store_put_s"] + setup["warm_s"]
            <= result["metrics"]["setup_s"]["value"])


def test_traced_run_reads_per_layer_metrics(tmp_path):
    result = _run(tmp_path, trace=True)
    assert result["correct"]
    assert {"map_host_s_per_GB", "map_device_sort_s_per_GB",
            "reduce_merge_s_per_GB", "durable_requests_per_GB",
            "partition_imbalance"} <= set(result["metrics"])
    assert result["metrics"]["partition_imbalance"]["value"] >= 1.0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_is_not_correct():
    config = dict(tiny.TINY, waves=2, reducers_per_worker=16)
    inp = gen.make_input(
        total_records=config["records_per_wave"] * config["waves"],
        records_per_object=config["input_records_per_partition"],
        payload_words=23, variant="uniform", seed=SEED, prefix="input/")
    outputs = reference.control_outputs(
        inp, records_per_wave=config["records_per_wave"], partitions=16)
    numbers = reference.compare(inp, outputs)
    assert not reference.passes(numbers)
    assert numbers["records_differing"] > 0 and numbers["order_breaks"] > 0


def _unsorted(monkeypatch):
    import jax.numpy as jnp

    from repro.core import external_sort as xs

    def identity(keys, ids, **_):
        return (keys, ids, jnp.full((1,), keys.shape[0], jnp.int32),
                jnp.bool_(False))

    monkeypatch.setattr(xs, "streaming_sort", identity)


def _half_left_out(monkeypatch):
    from repro.core import external_sort as xs

    sort = xs.WaveSorter.device_sort

    def half(self, keys, ids, **kw):
        sk, si, vcounts = sort(self, keys, ids, **kw)
        return sk, si, vcounts // 2

    monkeypatch.setattr(xs.WaveSorter, "device_sort", half)


def _record_altered(monkeypatch):
    from repro.shuffle import sort as ss

    consume = ss._SortMergeSink.consume
    done = []

    def altered(self, frags, *, final):
        body = consume(self, frags, final=final)
        if body and not done:
            done.append(True)
            rows = np.frombuffer(body, "<u4").copy()
            rows[5] ^= 1  # a payload word of the first record
            body = rows.tobytes()
        return body

    monkeypatch.setattr(ss._SortMergeSink, "consume", altered)


#: A child process runs the planted run: the unsorted fault makes the
#: program's merge spin forever, and only the end of a process stops it.
CHILD = """
import json, os, pathlib, sys
sys.path.insert(0, {tests!r})
import pytest
import test_chipbench_runs as t
from harness import runner
runner.GRACE_S = 3.0
patch = pytest.MonkeyPatch()
getattr(t, {plant!r})(patch)
result = t._run(pathlib.Path({tmp!r}))
print(json.dumps(result), flush=True)
os._exit(0)
"""


@pytest.mark.parametrize("plant", ["_unsorted", "_half_left_out",
                                   "_record_altered"])
def test_fault_makes_run_incorrect(tmp_path, plant):
    code = CHILD.format(tests=os.path.dirname(os.path.abspath(__file__)),
                        plant=plant, tmp=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not result["correct"]
    assert result["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in result["checks"].values())
