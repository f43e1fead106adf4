"""Chaos harness: adversarial schedules against the elastic fleet.

Not a pytest module (no `test_` prefix — run it directly):

    PYTHONPATH=src python tests/chaos.py --smoke   # CI: the short set
    PYTHONPATH=src python tests/chaos.py           # every schedule

Each schedule runs the CloudSort job under one injected failure mode —
task-budget kills, request-budget kills, a worker that keeps working but
goes HEARTBEAT-SILENT, a straggler store with speculation racing it,
mid-job admission/retirement, multi-worker kills, and process-fleet
kills (`os._exit`, no goodbye) — and then asserts the two invariants the
whole design hangs on:

  * the output layout (keys, CRC etags, sizes, part counts) is
    byte-identical to a clean single-host reference run, and
  * valsort accepts the result (globally sorted, checksum preserved).

Schedules also pin the OBSERVABILITY of each failure: the tracer must
carry the matching `cluster.*` events (heartbeat_miss, speculate,
spill_lost, worker_dead, ...) so operators can see what the recovery
machinery did, not just that bytes came out right.
"""
import os

# Before the first jax import: the schedules need an 8-device host mesh,
# emulated on the CPU backend (never a chip, whatever the environment).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

from repro.core.external_sort import ExternalSortPlan  # noqa: E402
from repro.core.compat import make_mesh  # noqa: E402
from repro.data import gensort, valsort  # noqa: E402
from repro.io.middleware import (FaultProfile, KillSwitchMiddleware,  # noqa: E402
                                 LatencyBandwidthMiddleware)
from repro.io.object_store import ObjectStore  # noqa: E402
from repro.obs.events import Tracer  # noqa: E402
from repro.shuffle.elastic import FleetPlan  # noqa: E402
from repro.shuffle.executor import (FaultyWorker, ThreadWorker,  # noqa: E402
                                    Worker, WorkerFailure)
from repro.shuffle.sort import sort_shuffle_job  # noqa: E402

PLAN = ExternalSortPlan(
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
N = 1 << 15  # 4 map tasks; 16 output partitions


class MuteWorker(Worker):
    """A worker that keeps WORKING but stops heartbeating after
    `mute_after_tasks` pops — the failure mode only the monitor can
    catch (the store keeps answering, so no request ever fails). The
    driver's `fence()` must then sever its store view so its in-flight
    attempts cannot reach a durable commit after it was declared dead.
    """

    def __init__(self, inner: Worker, *, mute_after_tasks: int):
        self.inner = inner
        self.name = inner.name
        self._kill = KillSwitchMiddleware(
            inner.store,
            exc_factory=lambda: WorkerFailure(
                f"{self.name}: fenced after heartbeat loss"))
        self.store = inner.store = self._kill
        self._lock = threading.Lock()
        self._remaining = mute_after_tasks
        self._muted = threading.Event()
        self._frozen = time.monotonic()

    def _gated(self, pop_next):
        def pop():
            task = pop_next()
            if task is not None:
                with self._lock:
                    self._remaining -= 1
                    if self._remaining <= 0 and not self._muted.is_set():
                        self._frozen = time.monotonic()
                        self._muted.set()
            return task
        return pop

    def run_map_phase(self, ctx, pop_next, on_done):
        self.inner.run_map_phase(ctx, self._gated(pop_next), on_done)

    def run_reduce_phase(self, ctx, pop_next, on_done):
        self.inner.run_reduce_phase(ctx, self._gated(pop_next), on_done)

    def last_beat(self):
        return self._frozen if self._muted.is_set() else time.monotonic()

    def fence(self):
        self._kill.trip()


class Harness:
    """One store + reference layout, many adversarial schedules."""

    def __init__(self):
        self.mesh = make_mesh((8,), ("w",))
        self.root = tempfile.mkdtemp(prefix="chaos-")
        self.store = ObjectStore(self.root)
        self.store.create_bucket("sort")
        self.in_ck, _ = gensort.write_to_store(
            self.store, "sort", PLAN.input_prefix, N,
            PLAN.input_records_per_partition, PLAN.payload_words)
        print("chaos: computing clean reference layout ...")
        sort_shuffle_job(self.store, "sort", mesh=self.mesh, axis_names="w",
                         plan=PLAN).run(workers=0)
        self.want = self.layout()
        assert len(self.want) == 16

    def layout(self):
        return [(m.key, m.etag, m.size, m.parts)
                for m in self.store.list_objects("sort", PLAN.output_prefix)]

    def run(self, crew, fleet, tracer):
        job = sort_shuffle_job(self.store, "sort", mesh=self.mesh,
                               axis_names="w", plan=PLAN, tracer=tracer)
        return job.run(worker_list=crew, fleet=fleet)

    def check_bytes(self, tag):
        assert self.layout() == self.want, f"{tag}: output bytes diverged"
        val = valsort.validate_from_store(self.store, "sort",
                                          PLAN.output_prefix, self.in_ck)
        assert val.ok and val.total_records == N, (tag, val)

    @staticmethod
    def events(tracer, name):
        return [e for e in tracer.log.events() if e["name"] == name]


# -- schedules (each: run, byte-check, event-check) -------------------------


def schedule_clean(h: Harness):
    """Baseline: the elastic driver with nothing injected."""
    tr = Tracer("chaos-clean")
    crew = [ThreadWorker(f"w{i}", h.store) for i in range(3)]
    crep = h.run(crew, FleetPlan(), tr)
    h.check_bytes("clean")
    assert not crep.failed_workers and crep.recovery_rounds == 0
    assert not h.events(tr, "cluster.worker_dead")


def schedule_task_kill(h: Harness):
    """w0 dies at its 7th task pop (inside reduce): spill-tier loss,
    lineage re-execution, reduce resumption."""
    tr = Tracer("chaos-task-kill")
    crew = [FaultyWorker(ThreadWorker("w0", h.store), fail_after_tasks=6),
            ThreadWorker("w1", h.store)]
    crep = h.run(crew, FleetPlan(), tr)
    h.check_bytes("task_kill")
    assert crep.failed_workers == ["w0"]
    assert crep.spill_lost_map_tasks >= 1, crep
    assert h.events(tr, "cluster.worker_dead")
    assert h.events(tr, "cluster.spill_lost")


def schedule_request_kill(h: Harness):
    """w1's store view dies mid-request-stream: in-flight sibling merges
    are severed with partial multipart sessions behind them."""
    tr = Tracer("chaos-request-kill")
    crew = [ThreadWorker("w0", h.store),
            FaultyWorker(ThreadWorker("w1", h.store), fail_after_requests=40),
            ThreadWorker("w2", h.store)]
    crep = h.run(crew, FleetPlan(), tr)
    h.check_bytes("request_kill")
    assert crep.failed_workers == ["w1"]
    assert h.events(tr, "cluster.worker_dead")


def schedule_heartbeat_mute(h: Harness):
    """w0 keeps working but goes silent: only the heartbeat monitor can
    declare it dead; the fence must stop its in-flight commits."""
    tr = Tracer("chaos-mute")
    crew = [MuteWorker(ThreadWorker("w0", h.store), mute_after_tasks=2),
            ThreadWorker("w1", h.store)]
    fleet = FleetPlan(heartbeat_timeout_s=0.5, monitor_interval_s=0.05)
    crep = h.run(crew, fleet, tr)
    h.check_bytes("heartbeat_mute")
    assert "w0" in crep.failed_workers, crep.failed_workers
    assert crep.heartbeat_misses >= 1, crep
    misses = h.events(tr, "cluster.heartbeat_miss")
    assert misses and misses[0]["worker"] == "w0"


def schedule_speculation(h: Harness):
    """One straggler HOST (latency-injected store view): speculation
    duplicates its laggards and the fast copy wins the commit race."""
    tr = Tracer("chaos-speculation")
    slow = LatencyBandwidthMiddleware(h.store, FaultProfile(latency_s=0.25))
    crew = [ThreadWorker("w0", h.store), ThreadWorker("w1", h.store),
            ThreadWorker("slow", slow)]
    fleet = FleetPlan(speculation=True, speculation_min_samples=3,
                      speculation_quantile=0.5, speculation_factor=2.0,
                      speculation_min_s=0.1)
    crep = h.run(crew, fleet, tr)
    h.check_bytes("speculation")
    assert not crep.failed_workers
    assert crep.speculated_tasks >= 1 and crep.speculation_wins >= 1, crep
    assert h.events(tr, "cluster.speculate")


def schedule_membership(h: Harness):
    """Scale events mid-job: retire w1 at the start, admit a late joiner
    while the phases run."""
    tr = Tracer("chaos-membership")
    job = sort_shuffle_job(h.store, "sort", mesh=h.mesh, axis_names="w",
                           plan=PLAN, tracer=tr)
    session = job.prepare(schedulers=2)
    crew = [ThreadWorker(f"w{i}", h.store) for i in range(2)]
    late = ThreadWorker("late", h.store)

    def membership():
        while getattr(session, "driver", None) is None:
            time.sleep(0.005)
        session.driver.retire("w1")
        session.driver.admit(late)

    t = threading.Thread(target=membership, daemon=True)
    t.start()
    crep = session.run_elastic(crew, FleetPlan())
    t.join()
    h.check_bytes("membership")
    assert crep.workers_admitted == 1 and crep.workers_retired == 1
    assert crep.per_worker_tasks.get("late", 0) >= 1, crep.per_worker_tasks
    assert h.events(tr, "cluster.worker_admitted")
    assert h.events(tr, "cluster.worker_retired")


def schedule_multi_kill(h: Harness):
    """Half the fleet dies (2 of 4, staggered): survivors absorb both
    spill losses and every re-executed wave."""
    tr = Tracer("chaos-multi-kill")
    crew = [FaultyWorker(ThreadWorker("w0", h.store), fail_after_tasks=3),
            ThreadWorker("w1", h.store),
            FaultyWorker(ThreadWorker("w2", h.store), fail_after_tasks=4),
            ThreadWorker("w3", h.store)]
    crep = h.run(crew, FleetPlan(), tr)
    h.check_bytes("multi_kill")
    assert sorted(crep.failed_workers) == ["w0", "w2"], crep.failed_workers
    assert len(h.events(tr, "cluster.worker_dead")) == 2


def schedule_process_kill(h: Harness):
    """Real process fleet; p0 os._exit(3)s at its 5th pop — no goodbye
    message, just EOF on the pipe — and its spill tier goes with it."""
    from repro.shuffle.procworker import ProcessWorker

    tr = Tracer("chaos-process-kill")
    crew = [ProcessWorker("p0", store=h.store, bucket="sort", plan=PLAN,
                          die_after_tasks=4),
            ProcessWorker("p1", store=h.store, bucket="sort", plan=PLAN)]
    try:
        crep = h.run(crew, FleetPlan(), tr)
    finally:
        for wk in crew:
            wk.close()
    h.check_bytes("process_kill")
    assert crep.failed_workers == ["p0"], crep.failed_workers
    assert crep.spill_lost_map_tasks >= 1 and crep.recovery_rounds >= 1, crep
    assert h.events(tr, "cluster.spill_lost")


def schedule_process_map_gate(h: Harness):
    """PR-8 pin: the process child's MAP chunk loop must poll the
    speculation commit gate per fetched chunk. An always-False gate
    (every attempt already lost its race) must make the child abandon
    every task at its FIRST gated read — zero confirmations, zero spill
    bytes — and the gate RPC must have been consulted for every task."""
    import dataclasses

    from repro.shuffle import executor as ex
    from repro.shuffle.procworker import ProcessWorker

    plan = dataclasses.replace(PLAN, spill_prefix="gate-spill/",
                               output_prefix="gate-output/")
    session = sort_shuffle_job(h.store, "sort", mesh=h.mesh, axis_names="w",
                               plan=plan).prepare()
    calls = []
    lock = threading.Lock()

    def gate(worker, g):
        with lock:
            calls.append((worker, g))
        return False  # every attempt has already lost: must abandon

    ctx = ex.WorkerContext(
        plan=plan, bucket="sort", map_op=session.job.map_op,
        reduce_shared=session.shared, timeline=session.timeline,
        control=session.control, num_map_tasks=session.num_tasks,
        map_commit_gate=gate)
    tasks = iter(range(session.num_tasks))
    done = []
    wk = ProcessWorker("pg0", store=h.store, bucket="sort", plan=plan)
    try:
        wk.run_map_phase(ctx, lambda: next(tasks, None), done.append)
    finally:
        wk.close()
    assert not done, f"lost attempts confirmed map tasks: {done}"
    assert {g for _, g in calls} == set(range(session.num_tasks)), calls
    spills = h.store.list_objects("sort", plan.spill_prefix)
    assert not list(spills), "abandoned map attempts spilled bytes"


def schedule_process_map_speculation(h: Harness):
    """End-to-end flavour of the same pin: a straggling PROCESS worker's
    map task is speculated, the fast copy commits first, and the
    straggler's in-flight attempt abandons mid-fetch via the commit RPC
    instead of streaming its whole wave."""
    from repro.shuffle.procworker import ProcessWorker

    tr = Tracer("chaos-process-map-speculation")
    crew = [ProcessWorker("p0", store=h.store, bucket="sort", plan=PLAN,
                          fault={"latency_s": 0.3}),
            ProcessWorker("p1", store=h.store, bucket="sort", plan=PLAN)]
    fleet = FleetPlan(speculation=True, speculation_min_samples=2,
                      speculation_quantile=0.5, speculation_factor=1.5,
                      speculation_min_s=0.1)
    try:
        crep = h.run(crew, fleet, tr)
    finally:
        for wk in crew:
            wk.close()
    h.check_bytes("process_map_speculation")
    assert not crep.failed_workers, crep.failed_workers
    assert crep.speculated_tasks >= 1 and crep.speculation_wins >= 1, crep
    spec = h.events(tr, "cluster.speculate")
    assert any(e.get("phase") == "map" for e in spec), spec


def schedule_recursive_kill(h: Harness):
    """A worker dies mid-round of a RECURSIVE shuffle: duplicate-heavy
    input whose hot partition exceeds the reduce budget, so the sort
    runs sampled boundaries + multi-round recursion (shuffle/recursive)
    — and w0's death must leave every round's output byte/etag-identical
    to the clean reference, with the recovery AND the recursion both
    visible on the tracer."""
    import dataclasses

    from repro.shuffle.recursive import recursive_sort

    plan = dataclasses.replace(
        PLAN,
        input_prefix="rec-input/", spill_prefix="rec-spill/",
        output_prefix="rec-output/",
        capacity_factor=4.0, sample_fraction=1 / 16, max_rounds=3)
    in_ck, _ = gensort.write_to_store(
        h.store, "sort", plan.input_prefix, N,
        plan.input_records_per_partition, plan.payload_words,
        skew="dup", skew_seed=3)

    def rec_layout():
        return [(m.key, m.etag, m.size, m.parts)
                for m in h.store.list_objects("sort", plan.output_prefix)]

    clean = recursive_sort(h.store, "sort", mesh=h.mesh, axis_names="w",
                           plan=plan, workers=0)
    assert clean.num_rounds >= 3 and clean.recursed, clean.rounds
    want = rec_layout()
    val = valsort.validate_from_store(h.store, "sort", plan.output_prefix,
                                      in_ck)
    assert val.ok and val.total_records == N, val

    tr = Tracer("chaos-recursive-kill")
    crew = [FaultyWorker(ThreadWorker("w0", h.store), fail_after_tasks=4),
            ThreadWorker("w1", h.store)]
    crep = recursive_sort(h.store, "sort", mesh=h.mesh, axis_names="w",
                          plan=plan, worker_list=crew, fleet=FleetPlan(),
                          tracer=tr)
    assert rec_layout() == want, "recursive_kill: output bytes diverged"
    val = valsort.validate_from_store(h.store, "sort", plan.output_prefix,
                                      in_ck)
    assert val.ok and val.total_records == N, val
    assert any("w0" in getattr(r, "failed_workers", [])
               for _, _, r in crep.rounds), "w0 never died"
    assert h.events(tr, "cluster.worker_dead")
    rounds = h.events(tr, "recursive.round")
    assert len(rounds) == len(crep.rounds) >= 3, rounds
    assert h.events(tr, "recursive.redirect")


SMOKE = [schedule_clean, schedule_task_kill, schedule_heartbeat_mute,
         schedule_speculation, schedule_process_map_gate,
         schedule_recursive_kill]
FULL = SMOKE + [schedule_request_kill, schedule_membership,
                schedule_multi_kill, schedule_process_kill,
                schedule_process_map_speculation]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run the short CI set only")
    args = ap.parse_args(argv)
    schedules = SMOKE if args.smoke else FULL
    h = Harness()
    for sched in schedules:
        t0 = time.perf_counter()
        sched(h)
        print(f"chaos: {sched.__name__} OK "
              f"({time.perf_counter() - t0:.1f}s)")
    print(f"chaos: {len(schedules)} schedules passed, output byte-identical "
          "under every one")


if __name__ == "__main__":
    main()
