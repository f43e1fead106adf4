"""One run of one cell: set-up, the measured window, the check, the metrics.

Set-up makes the input from the seed on the device and puts it into a tiered
store built from the program's public classes: a durable tier in memory
(standing for S3, holding input and output) and a filesystem spill tier under
the checkout (standing for the worker's local NVMe). It then runs the
cell's wave sort once at its real shape, which compiles it or loads it from
JAX's persistent compilation cache.

The window runs whole jobs back to back through the program's entry,
`repro.shuffle.recursive.recursive_sort(..., workers=0)`, on the same input.
Another job starts only while the last one, run again, would end inside
`--seconds`; the first always runs. After the window the output is held to
the plain reference (harness/reference.py) and the cell's metrics are read
by their readers (metrics/<name>.py).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import resource
import shutil
import sys
import threading
import time
import traceback

import numpy as np

from harness import gen, reference, tables
from harness import trace as tr

BUCKET = "cloudsort"
MARKER = "chipbench.clock"
#: How long past the window's close a job may still end before the run
#: reports it lost.
GRACE_S = 60.0
#: Program spans that alias another span and would only double a label.
ALIAS_SPANS = ("map.compute",)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Job:
    """One job of the window, with the spans its tracer recorded."""

    start: float  # perf_counter
    end: float
    origin: float  # the job tracer's perf_counter origin
    events: list[dict]
    gauges: dict[str, float]
    etags: dict[str, str] = dataclasses.field(default_factory=dict)

    def spans(self):
        """(name, start, end) in perf_counter seconds, every span event."""
        return [(ev["name"], self.origin + ev["t"],
                 self.origin + ev["t"] + ev["dur"])
                for ev in self.events if ev["dur"] > 0]


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    config: dict
    chips: int
    jobs: list[Job]
    records_per_job: int
    record_bytes: int
    durable_gets: int  # durable-tier requests over the window
    durable_puts: int
    partition_records: list[int]  # output partitions of the last job
    setup_s: float
    prices: dict | None  # harness/tables.prices; None in the CPU tests
    device_trace: dict | None  # harness/trace.reduce_trace, traced runs

    @property
    def window_s(self) -> float:
        return self.jobs[-1].end - self.jobs[0].start

    @property
    def input_bytes(self) -> int:
        return len(self.jobs) * self.records_per_job * self.record_bytes

    def span_seconds(self, names) -> float:
        return sum(e - s for job in self.jobs for n, s, e in job.spans()
                   if n in names)

    def gauge_mean(self, key: str) -> float | None:
        values = [job.gauges[key] for job in self.jobs if key in job.gauges]
        return sum(values) / len(values) if values else None


class CompileLog:
    """Counts, per phase, the programs JAX had to obtain (each an XLA
    compile or a load from the persistent compilation cache), the cache
    loads among them, and the seconds spent obtaining them."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "programs",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loads"}

    def __init__(self):
        import jax.monitoring as monitoring

        self.phase = "setup"
        self.counts: dict[tuple[str, str], float] = {}
        self._monitoring = monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        kind = self.EVENTS.get(event)
        if kind:
            for key, add in (((self.phase, kind), 1),
                             ((self.phase, kind + "_s"), duration)):
                self.counts[key] = self.counts.get(key, 0) + add

    def parts(self, phase: str) -> dict:
        """XLA compiles, persistent-cache loads and the seconds spent
        obtaining programs in `phase`."""
        get = lambda k: self.counts.get((phase, k), 0)  # noqa: E731
        return {"xla_compiles": get("programs") - get("loads"),
                "cache_loads": get("loads"), "programs_s": get("programs_s")}

    def summary(self, phase: str) -> str:
        p = self.parts(phase)
        return (f"XLA compiles {p['xla_compiles']}, cache loads "
                f"{p['cache_loads']}, {p['programs_s']:.4f}s obtaining "
                "programs")

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_event)


def plan_from(config: dict):
    """The program's ExternalSortPlan with every field the config names."""
    from repro.core.external_sort import ExternalSortPlan

    fields = {f.name for f in dataclasses.fields(ExternalSortPlan)}
    return ExternalSortPlan(**{k: v for k, v in config.items()
                               if k in fields})


def build_store(spill_root: str, plan):
    """TieredStore(Metrics(Memory), Metrics(Filesystem)), spill routed to
    the filesystem tier."""
    from repro.io.backends import FilesystemBackend, MemoryBackend
    from repro.io.middleware import MetricsMiddleware
    from repro.io.tiered import TieredStore

    return TieredStore(MetricsMiddleware(MemoryBackend()),
                       MetricsMiddleware(FilesystemBackend(spill_root)),
                       ssd_prefixes=(plan.spill_prefix,))


def _warm(plan, mesh, inp: gen.Input) -> None:
    """Run the cell's wave sort once at its real shape on the first wave."""
    from repro.core.external_sort import WaveSorter

    if plan.sample_fraction > 0:
        raise ValueError("no warm-up for a sampling plan's wave sort yet")
    sorter = WaveSorter(plan, mesh, "w")
    wave = [inp.rows(p) for p in range(plan.records_per_wave
                                       // plan.input_records_per_partition)]
    sorter.device_sort(np.concatenate([r[:, 0] for r in wave]),
                       np.concatenate([r[:, 1] for r in wave]))


@dataclasses.dataclass(frozen=True)
class Options:
    seed: int
    seconds: float  # no job starts that would end past this
    trace: bool
    t_start: float  # perf_counter at process start
    t_import: float  # perf_counter before JAX was asked for its devices
    workdir: str  # spill tier and profiler trace; removed at the end


class JobLost(RuntimeError):
    """A job that had not ended GRACE_S past the window's close."""


def open_chips(chips: int):
    """The first `chips` TPU chips and their prices. Exits, before any
    result is printed, when JAX finds no TPU or fewer chips; a chip kind
    the price table does not hold is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX platform is "
                         f"{devices[0].platform!r}, not tpu; no run")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: {chips} chips needed, JAX reports "
                         f"{len(devices)}")
    return devices[:chips], tables.prices(devices[0].device_kind)


def run_cell(bench, name: str, devices, prices, opts: Options) -> dict:
    """Run cell `name` once on `devices`; returns the result object (last
    stdout line). A job that never ends is left running on a daemon thread,
    named `chipbench-job`, and the run reports it; the caller then ends the
    process without waiting for it."""
    cell = bench.cell(name)
    config = bench.config(cell["config"])
    if int(config["mesh_workers"]) > len(devices):
        raise SystemExit(f"chipbench: config {cell['config']} has "
                         f"{config['mesh_workers']} mesh workers, cell "
                         f"{name} {len(devices)} chips")
    stamps = {"start": opts.t_start, "import": opts.t_import,
              "init": time.perf_counter()}
    compiles = CompileLog()
    shutil.rmtree(opts.workdir, ignore_errors=True)
    try:
        return _run(bench, name, cell, config, devices, prices,
                    compiles, opts, stamps)
    finally:
        compiles.close()
        shutil.rmtree(opts.workdir, ignore_errors=True)


def _job(store, mesh, plan, tracer, timeout: float):
    """One job through the program's entry, on a thread of its own so that
    a job that never ends cannot hold the run past its deadline."""
    from repro.shuffle.recursive import recursive_sort

    box: dict = {}

    def target():
        try:
            box["report"] = recursive_sort(store, BUCKET, mesh=mesh,
                                           axis_names="w", plan=plan,
                                           workers=0, tracer=tracer)
        except BaseException as e:  # handed to the caller, which reports it
            box["error"] = e

    thread = threading.Thread(target=target, name="chipbench-job",
                              daemon=True)
    thread.start()
    thread.join(max(timeout, 0.0))
    if thread.is_alive():
        raise JobLost(f"job still running at its deadline, {timeout:.1f}s "
                      "after it started")
    if "error" in box:
        raise box["error"]
    return box["report"]


def _setup(config, traffic, devices, compiles, opts, stamps):
    """Input, store and mesh for the window; the wave sort warmed."""
    from repro.core.compat import make_mesh

    plan = plan_from(config)
    total = plan.records_per_wave * int(config["waves"])
    inp = gen.make_input(total_records=total,
                         records_per_object=plan.input_records_per_partition,
                         payload_words=plan.payload_words,
                         variant=traffic["keys"], seed=opts.seed,
                         prefix=plan.input_prefix)
    t_gen = time.perf_counter()
    store = build_store(os.path.join(opts.workdir, "spill"), plan)
    store.create_bucket(BUCKET)
    for key, data in inp.objects:
        store.put(BUCKET, key, data)
    t_put = time.perf_counter()
    width = int(config["mesh_workers"])
    mesh = make_mesh((width,), ("w",), devices=devices[:width])
    _warm(plan, mesh, inp)
    parts = {"import_s": stamps["import"] - stamps["start"],
             "device_init_s": stamps["init"] - stamps["import"],
             "input_gen_s": t_gen - stamps["init"],
             "store_put_s": t_put - t_gen,
             "warm_s": time.perf_counter() - t_put,
             **compiles.parts("setup")}
    for k, v in parts.items():
        log(f"setup {k} {v:.4f}")
    log(f"setup input {total} records, "
        f"{total * plan.record_bytes / 1e9:.3f} GB")
    return plan, inp, store, mesh, parts


@dataclasses.dataclass
class Window:
    jobs: list[Job]
    failed: int  # jobs that raised or never ended
    start: float  # perf_counter
    durable_gets: int
    durable_puts: int
    t_mark: float | None  # perf_counter of the trace's clock marker


def _window(store, mesh, plan, compiles, opts, trace_dir) -> Window:
    """Whole jobs back to back (see the module docstring)."""
    import jax

    from repro.obs.events import Tracer

    before = after = store.durable.stats_snapshot()
    t_mark = None
    if opts.trace:
        profile = jax.profiler.ProfileOptions()
        profile.python_tracer_level = 0
        profile.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=profile)
        t_mark = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARKER):
            pass
    compiles.phase = "window"
    jobs: list[Job] = []
    failed = 0
    t_window = time.perf_counter()
    deadline = t_window + opts.seconds + GRACE_S
    while True:
        tracer = Tracer(job=f"job-{len(jobs)}", max_events=1 << 20)
        t = time.perf_counter()
        try:
            report = _job(store, mesh, plan, tracer, deadline - t)
        except Exception:  # the run reports it: correct is false
            log(traceback.format_exc())
            failed += 1
            break
        t_end = time.perf_counter()
        after = store.durable.stats_snapshot()
        jobs.append(Job(start=t, end=t_end, origin=tracer.origin,
                        events=tracer.log.events(),
                        gauges=tracer.registry.snapshot()["gauges"],
                        etags={m.key: m.etag for m in store.list_objects(
                            BUCKET, plan.output_prefix)}))
        log(f"job {len(jobs) - 1} seconds {t_end - t:.4f} map "
            f"{report.report.map_seconds:.4f} reduce "
            f"{report.report.reduce_seconds:.4f} sample "
            f"{report.sample.seconds if report.sample else 0.0:.4f}")
        by_span: dict[str, float] = {}
        for n, s, e in jobs[-1].spans():
            by_span[n] = by_span.get(n, 0.0) + e - s
        log(f"job {len(jobs) - 1} span seconds " + " ".join(
            f"{n} {v:.4f}" for n, v in sorted(by_span.items())))
        if t_end + (t_end - t) > t_window + opts.seconds:
            break
    compiles.phase = "after"
    if opts.trace:
        jax.profiler.stop_trace()
    log(f"window jobs {len(jobs)} failed {failed} seconds "
        f"{(jobs[-1].end - t_window) if jobs else 0.0:.4f} "
        f"({compiles.summary('window')}), host peak_rss_bytes "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
    return Window(jobs=jobs, failed=failed, start=t_window,
                  durable_gets=after.get_requests - before.get_requests,
                  durable_puts=after.put_requests - before.put_requests,
                  t_mark=t_mark)


def _run(bench, name, cell, config, devices, prices, compiles, opts,
         stamps) -> dict:
    plan, inp, store, mesh, setup = _setup(config, bench.traffic(cell["traffic"]),
                                    devices, compiles, opts, stamps)
    trace_dir = os.path.join(opts.workdir, "trace")
    win = _window(store, mesh, plan, compiles, opts, trace_dir)
    jobs = win.jobs
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # The check, once the window has closed and the device peak is read.
    if not win.failed:
        shutil.rmtree(os.path.join(opts.workdir, "spill"), ignore_errors=True)
    gc.collect()
    t_check = time.perf_counter()
    metas = store.list_objects(BUCKET, plan.output_prefix)
    etags_differing = sum(job.etags != jobs[-1].etags for job in jobs)
    numbers = reference.compare(
        inp, (store.get(BUCKET, m.key) for m in metas),
        etags_differing=etags_differing, jobs_failed=win.failed)
    log(f"check seconds {time.perf_counter() - t_check:.4f}")
    # A job fails when it raised or never ended, when its etags differ
    # from the last job's, or (the last job) when its records are wrong.
    records_wrong = any(numbers[k] for k in reference.RECORD_CHECKS)
    result: dict = {"correct": bool(jobs) and reference.passes(numbers),
                    "attempted": len(jobs) + win.failed,
                    "failed": win.failed + etags_differing
                    + int(records_wrong),
                    "metrics": {}}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if jobs:
        run = Run(config=config, chips=len(devices), jobs=jobs,
                  records_per_job=inp.total_records,
                  record_bytes=plan.record_bytes,
                  durable_gets=win.durable_gets,
                  durable_puts=win.durable_puts,
                  partition_records=[(m.size - gen.HEADER_BYTES)
                                     // plan.record_bytes for m in metas],
                  setup_s=win.start - stamps["start"], prices=prices,
                  device_trace=None)
        if opts.trace:
            run.device_trace = _reduce(trace_dir, win.t_mark, jobs)
            device["busy_s"] = run.device_trace["busy_s"]
            device["window_s"] = run.device_trace["window_s"]
            result["breakdown"] = {
                "device_ops": run.device_trace["device_ops"],
                "idle_gaps": run.device_trace["idle_gaps"]}
        for metric in bench.metrics(name, opts.trace):
            value = bench.reader(metric["name"])(run)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": float(value), "unit": metric["unit"]}
    result["device"] = device
    # Set-up in parts: the programs_s of a first run in a checkout is its
    # compile, which setup_s holds and the warm runs' loads do not.
    result["setup"] = setup
    log(f"host peak_rss_bytes "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
    result["checks"] = {k: {"value": numbers[k],
                            "limit": reference.LIMITS[k]} for k in numbers}
    for k, v in result["checks"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def _reduce(trace_dir: str, t_mark: float, jobs: list[Job]) -> dict:
    """Device busy time, top ops and labelled idle gaps of the window."""
    path = tr.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    ops, mark = tr.load_xplane(path, MARKER)
    if mark is None:
        raise RuntimeError(f"no {MARKER} marker in {path}")
    offset = mark - t_mark * 1e9
    spans = [(n, s * 1e9 + offset, e * 1e9 + offset)
             for job in jobs for n, s, e in job.spans()
             if n not in ALIAS_SPANS]
    window = (jobs[0].start * 1e9 + offset, jobs[-1].end * 1e9 + offset)
    return tr.reduce_trace(ops, spans, window)
