"""Out-of-core external sort: the dataset lives in the object store, not HBM.

This module is the CloudSort *workload*: the wave/spill layout, the
device-mesh map body (WaveSorter), and the ExternalSortPlan schedule.
Since the shuffle-as-a-library refactor, the generic machinery that used
to live here — span timelines, job control, the AdaptiveBudgetGovernor,
streaming run cursors, the reduce scheduler, the staged map loop, the
single-host/cluster drivers — is the library (src/repro/shuffle/), and
the sort is one instantiation of it:

    from repro.shuffle.sort import sort_shuffle_job
    report = sort_shuffle_job(store, bucket, mesh=mesh, axis_names="w",
                              plan=plan).run(workers=N)

`external_sort()` below is kept as a thin deprecated shim over exactly
that call (workers=0), byte- and etag-identical to the pre-refactor
driver; core/cluster.cluster_external_sort is the cluster-mode shim.

Paper mapping (unchanged by the refactor):

  map waves (§2.3, §2.5): input partitions stream from the store in ranged
      chunks (io/backends.get_chunks — one GET per chunk, the paper's
      "120 chunks" map download), double-buffered against device compute
      (io/staging.prefetch, retry-aware against transient store stalls).
      Wave assembly is zero-copy: each chunk decodes straight into one
      preallocated interleaved-row buffer (io/records.StreamDecoder), so
      a wave's bytes are copied once off the wire instead of through
      b"".join + np.concatenate staging copies. Each wave runs the
      in-memory two-stage streaming exoshuffle (core/streaming.py), after
      which every worker holds one globally range-partitioned sorted run;
      each shuffled record's row is located by O(1) id-offset arithmetic
      (gensort ids are contiguous per wave) instead of a per-wave argsort,
      and the run is gathered whole from the wave's row buffer straight
      into its spill object.

  spill (§2.3): each worker's merged run is written back under
      plan.spill_prefix as one sorted run object. Against a TieredStore
      (io/tiered.py) that prefix routes to the local-SSD tier — the
      paper's actual spill target — while input/output keys stay on the
      durable (S3-like, throttled, billed) tier. Per-reducer offsets into
      the run are recorded in the object's manifest metadata; writes are
      write-behind via io/staging.AsyncWriter so upload overlaps the next
      wave's sort.

  reduce (§2.4): a scheduler runs up to plan.parallel_reducers streaming
      k-way merges CONCURRENTLY per worker (shuffle/runtime.ReduceScheduler
      driving shuffle/sort.MergeReduceOp) — the paper's "all output
      partitions at once" reduce stage, the scheduling freedom
      shuffle-as-a-library buys (Exoshuffle §4). Each active reducer
      fetches its slice of every spilled run in bounded ranged chunks,
      merges buffered records up to the smallest last-loaded key over
      still-active runs, and streams merged bytes into an incremental
      multipart upload fanned out over plan.part_upload_fanout threads.

Plan knobs and their invariants (the reduce-side memory/throughput
contract; see ExternalSortPlan for the map-side knobs):

  parallel_reducers — number of streaming k-way merges one scheduler runs
      concurrently. Output bytes are schedule-independent: partitions are
      independent objects and part payloads are sliced at fixed
      output_part_records boundaries, so ANY parallelism (and any cluster
      worker count) yields byte- and etag-identical partitions.

  part_upload_fanout — out-of-order part-indexed multipart uploads in
      flight per partition (S3 UploadPart semantics; assembly order is
      decided by part index at complete(), never by wire order).

  merge_chunk_bytes — hard CAP on the per-run fetch granularity of the
      streaming merge. Without a budget every cursor buffers at most this
      many decoded bytes, so per-merge peak <= runs x merge_chunk_bytes.

  reduce_memory_budget_bytes — global decoded-merge-buffer budget across
      ALL concurrently active reducers (0 = uncapped). Apportionment is
      ADAPTIVE (shuffle/runtime.AdaptiveBudgetGovernor): each registering
      reducer starts from the static fair share budget/slots, and as
      reducers retire their share is re-apportioned to still-active
      merges — chunk sizes grow mid-merge (up to merge_chunk_bytes), so
      tail stragglers fetch bigger chunks instead of leaving freed budget
      idle. The invariant is provable, not just measured — see the
      governor's docstring — and the measured all-reducer peak
      (reduce_peak_merge_bytes) never exceeds the budget.

Every phase records wall-clock spans (map wait/compute/spill, reduce
fetch/merge/upload) into the report's span timeline, so map/reduce
overlap is measured, not asserted. Every store interaction is
request-accounted, so the Table-2 TCO can be computed from *measured*
GET/PUT counts (core/cost_model.measured_cloudsort_tco, or
.measured_tiered_cloudsort_tco for per-tier legs) instead of the paper's
hardcoded 6M/1M constants.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import warnings
from typing import Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.exoshuffle import ShuffleConfig
from repro.core.streaming import streaming_sort
from repro.io import records as rec
from repro.io import staging
from repro.io.backends import StoreBackend
from repro.shuffle import runtime as _rt
from repro.shuffle.api import (ShuffleReport, require,
                               validate_dataflow_plan)

# Backwards-compatible re-exports: this machinery moved to the shuffle
# library (shuffle/runtime.py) when the generic dataflow API was carved
# out; the old names keep working for existing callers.
PhaseTimeline = _rt.PhaseTimeline
JobControl = _rt.JobControl
AdaptiveBudgetGovernor = _rt.AdaptiveBudgetGovernor
ReduceShared = _rt.ReduceShared
ReduceScheduler = _rt.ReduceScheduler
_PeakTracker = _rt.PeakTracker
_RunCursor = _rt.RunCursor
_SiblingFailed = _rt.SiblingFailed
_reduce_chunking = _rt.reduce_chunking
_merge_fragments = _rt.merge_fragments
_timed_part = _rt.timed_part
_timed_spill = _rt.timed_put
_finalize_session = _rt.finalize_session

#: The run report (renamed ShuffleReport when the library was carved
#: out — same fields, every workload reports through it).
ExternalSortReport = ShuffleReport


@dataclasses.dataclass(frozen=True)
class ExternalSortPlan:
    """Out-of-core schedule: what fits in HBM and how the store is laid out.

    records_per_wave is the device-resident working set — the analogue of
    the paper's (map tasks in flight) x (2 GB block) bound.
    merge_chunk_bytes is the reduce-side counterpart: the per-run fetch
    granularity cap of the streaming merge. parallel_reducers streaming
    merges run concurrently; with reduce_memory_budget_bytes set, the
    global budget is apportioned across them by the adaptive governor
    (initial per-run chunk = budget / (slots x runs), capped at
    merge_chunk_bytes, growing as reducers retire), so the summed decoded
    merge-buffer bytes across all active reducers stay within the budget
    — not parallelism x partition size. (The budget governs the merge
    *buffers*; each active reducer additionally holds up to ~one encoded
    output part being filled plus max_inflight_writes parts awaiting
    upload.)

    map_pipeline overlaps each wave's host decode, device sort, and
    spill encode across tasks (shuffle/runtime's staged map executor);
    spill bytes and offsets are identical either way — the knob only
    changes wall-clock concurrency. reduce_merge_impl selects the
    emit-window merge backend: "numpy" is the reference host argsort
    merge; "device" runs the kernels/kway_merge bitonic tournament on a
    one-thread merge stage, double-buffered so window i's merge+encode
    overlaps window i+1's ranged-GET fetches (byte/etag-identical
    output, one extra in-flight window of decoded fragments on top of
    the governor's accounting). The device merge's kernel lowering
    follows `impl` ("pallas" = the Pallas network, jit-compiled on CPU;
    "ref" = the lax.sort oracle).
    """

    records_per_wave: int  # device working set (records, across the mesh)
    num_rounds: int = 2  # streaming_sort rounds within a wave
    reducers_per_worker: int = 1  # R1; R = W * R1 output partitions
    payload_words: int = 4  # u32 payload words per record
    impl: str = "ref"  # kernel implementation ("ref" | "pallas")
    capacity_factor: float = 1.5
    input_prefix: str = "input/"
    spill_prefix: str = "spill/"
    output_prefix: str = "output/"
    input_records_per_partition: int = 1 << 13  # gensort object size
    output_part_records: int = 1 << 13  # multipart-upload part size
    store_chunk_bytes: int = 256 << 10  # map download GET granularity
    merge_chunk_bytes: int = 64 << 10  # reduce per-run fetch granularity (cap)
    prefetch_depth: int = 2  # double buffering
    max_inflight_writes: int = 2  # spill/per-partition part backpressure
    io_retries: int = 2  # staging-level re-reads of a failed wave load
    parallel_reducers: int = 4  # concurrent streaming merges (reduce pool)
    reduce_memory_budget_bytes: int = 0  # global merge budget; 0 = uncapped
    part_upload_fanout: int = 2  # out-of-order part uploads per partition
    map_pipeline: bool = True  # overlap decode/device-sort/encode across waves
    reduce_merge_impl: str = "numpy"  # emit-window merge ("numpy" | "device")
    # Skew-adaptive knobs, consumed by shuffle/recursive.recursive_sort:
    # sample_fraction > 0 runs a sampling pre-pass (ranged GETs over that
    # fraction of input records, traced/billed as phase "sample") whose
    # quantiles become the partition boundaries; max_rounds > 1 allows
    # partitions whose merged size exceeds reduce_memory_budget_bytes to
    # be re-shuffled by the next key bits as composed child ShuffleJobs.
    sample_fraction: float = 0.0  # fraction of input records to sample
    max_rounds: int = 1  # recursive shuffle depth (1 = single pass)

    @property
    def record_bytes(self) -> int:
        return rec.record_bytes(self.payload_words)

    def validate(self) -> None:
        """Mesh-independent plan validation (ValueError with the knob
        name and value — never an assert). The mesh-dependent checks
        (wave/round divisibility) run in WaveSorter, which knows the
        mesh width."""
        validate_dataflow_plan(self)
        require(self.records_per_wave >= 1, "records_per_wave",
                self.records_per_wave, "must hold >= 1 record per wave")
        require(self.num_rounds >= 1, "num_rounds", self.num_rounds,
                "must run >= 1 streaming round per wave")
        require(self.reducers_per_worker >= 1, "reducers_per_worker",
                self.reducers_per_worker, "must be >= 1 (R1)")
        require(self.input_records_per_partition >= 1,
                "input_records_per_partition",
                self.input_records_per_partition, "must be >= 1")
        require(self.capacity_factor > 0, "capacity_factor",
                self.capacity_factor, "must be > 0")
        require(self.reduce_merge_impl in ("numpy", "device"),
                "reduce_merge_impl", self.reduce_merge_impl,
                'must be "numpy" (host argsort merge) or "device" '
                "(kernels/kway_merge tournament, double-buffered)")
        require(0.0 <= self.sample_fraction <= 1.0, "sample_fraction",
                self.sample_fraction,
                "must be a fraction of input records in [0, 1]")
        require(self.max_rounds >= 1, "max_rounds", self.max_rounds,
                "must allow >= 1 shuffle round")
        require(self.max_rounds == 1 or self.reduce_memory_budget_bytes > 0,
                "max_rounds", self.max_rounds,
                "recursive rounds need reduce_memory_budget_bytes > 0 — "
                "the budget is the oversize criterion that triggers a "
                "re-shuffle")


def _spill_key(plan: ExternalSortPlan, wave: int, worker: int) -> str:
    return f"{plan.spill_prefix}wave-{wave:04d}/w-{worker:03d}"


def _output_key(plan: ExternalSortPlan, reducer: int) -> str:
    return f"{plan.output_prefix}part-{reducer:05d}"


def _group_waves(inputs, counts, records_per_wave: int):
    """Tile the key-ordered input objects into equal-record waves.

    ValueError, not assert: the tiling contract must survive python -O —
    a silently mis-tiled wave would sort fine and fail only at valsort.
    """
    waves, cur, acc = [], [], 0
    for meta, c in zip(inputs, counts):
        cur.append(meta)
        acc += c
        require(acc <= records_per_wave, "records_per_wave",
                records_per_wave,
                "input partitions must tile it exactly "
                f"(partition {meta.key} overflows the wave)")
        if acc == records_per_wave:
            waves.append(cur)
            cur, acc = [], 0
    require(not cur, "records_per_wave", records_per_wave,
            "total input records must be a multiple of it")
    return waves


def _contiguous_id_base(ids: np.ndarray) -> int | None:
    """Base id when a wave's ids are exactly arange(base, base + n).

    gensort assigns ids sequentially across key-ordered input partitions
    (data/gensort.write_to_store), so every wave decodes to a contiguous
    ascending id range: the payload row of shuffled record id is then
    just (id - base) — O(1) index arithmetic per record instead of the
    argsort + searchsorted gather (O(n log n), random access). One
    vectorized equality pass verifies the assumption; any other id layout
    falls back to the general gather.
    """
    n = ids.size
    if n == 0:
        return None
    base = int(ids[0])
    # Unwrapped comparison on purpose: a range wrapping past 2^32 would
    # break the (id - base) gather below, so it must take the fallback.
    if base + n - 1 != int(ids[-1]):
        return None
    expect = np.uint32(base) + np.arange(n, dtype=np.uint32)
    if not bool(np.array_equal(np.asarray(ids, dtype=np.uint32), expect)):
        return None
    return base


def _untimed(phase: str, worker: str = "", **attrs):
    """PhaseTimeline.span's stand-in where nothing records spans."""
    return contextlib.nullcontext(attrs)


def _validate_plan(plan: ExternalSortPlan, w: int) -> None:
    """Plan validation shared by every sort entry point, including the
    mesh-dependent divisibility check. ValueError, not assert: must
    survive python -O.
    """
    plan.validate()
    require(plan.records_per_wave % (w * plan.num_rounds) == 0,
            "records_per_wave", plan.records_per_wave,
            f"must divide evenly into {w} mesh workers x "
            f"{plan.num_rounds} rounds")


class WaveSorter:
    """Map-side building block: load a wave zero-copy, sort it across the
    mesh, spill per-mesh-worker runs.

    Wrapped by shuffle/sort.SortMapOp, which is how the single-host and
    cluster drivers reach it. Deterministic by construction: the spilled
    run bytes and reducer offsets depend only on (wave contents, plan,
    mesh width) — never on which scheduler or emulated worker executes
    the wave — which is what keeps cluster output byte-identical to the
    single-host run at any worker count and under re-execution.
    """

    def __init__(self, plan: ExternalSortPlan, mesh: jax.sharding.Mesh,
                 axis_names: Sequence[str] | str,
                 boundaries: Sequence[int] | np.ndarray | None = None):
        axis = tuple([axis_names] if isinstance(axis_names, str)
                     else axis_names)
        self.plan = plan
        self.w = int(math.prod(mesh.shape[a] for a in axis))
        self.r1 = plan.reducers_per_worker
        self.pw = plan.payload_words
        _validate_plan(plan, self.w)
        # Explicit (sampled) reducer boundaries replace the equal split in
        # BOTH device routing (worker boundaries = every R1-th entry, via
        # the keyspace) and the host-side reducer_offsets searchsorted
        # below, so spill offsets stay bit-consistent with routing.
        self.cfg = ShuffleConfig(
            num_workers=self.w,
            reducers_per_worker=self.r1,
            capacity_factor=plan.capacity_factor,
            num_rounds=plan.num_rounds,
            impl=plan.impl,
            boundaries=(None if boundaries is None
                        else tuple(int(b) for b in np.asarray(boundaries))),
        )
        def wave_sort(k, i):
            return streaming_sort(k, i, mesh=mesh, axis_names=axis_names,
                                  num_rounds=plan.num_rounds, cfg=self.cfg)

        # A named function, so the device trace's module is jit_wave_sort.
        self._sort = jax.jit(wave_sort)
        # The wave sort's input layout: each mesh worker's shard of keys
        # and ids on its own device.
        self._sharding = NamedSharding(mesh, P(axis))
        self._local_bounds = (
            np.asarray(self.cfg.keyspace.local_reducer_boundaries())
            if self.r1 > 1 else None
        )  # (W, R1-1)
        # The device mesh is ONE shared resource: concurrent executions of
        # the same multi-device collective program interleave their
        # per-device participant threads into XLA's rendezvous and
        # deadlock (and a real accelerator would serialize them anyway).
        # Emulated cluster workers therefore queue on this lock for the
        # sort step, and overlap on everything else — load, spill, reduce
        # — which is where worker-count scaling pays on a latency-bound
        # store.
        self._device_lock = threading.Lock()

    def load_wave(self, store: StoreBackend, bucket: str, objs):
        """Chunked-GET a wave's input objects into one preallocated
        interleaved-row buffer (zero-copy decode) and return it: the
        (n, 2 + payload_words) u32 rows [key, id, payload...], the single
        owner of the wave's bytes."""
        plan = self.plan
        n_wave = sum(
            (m.size - rec.HEADER_BYTES) // plan.record_bytes for m in objs)
        rows = rec.alloc_rows(n_wave, self.pw)
        at = 0
        for m in objs:
            dec = rec.StreamDecoder(rows, at, what=m.key)
            for chunk in store.get_chunks(bucket, m.key, plan.store_chunk_bytes):
                dec.feed(chunk)
            at += dec.finish()
        return rows

    def device_sort(self, keys, ids, *, timeline: PhaseTimeline | None = None,
                    tag: str = ""):
        """Stage 1 of the map body: the mesh sort (serialized on the
        device lock), returned as host copies (sk, si, vcounts).

        With a timeline, the call is one map.device_sort span holding,
        in order, map.device_lock (the acquire), map.h2d (each mesh
        worker's shard of keys and ids to its own device, waited for),
        map.sort (the jitted sort, waited for) and map.d2h (the sorted
        run back to the host).

        map.sort carries the exchange's counts: `mesh_workers`,
        `exchange_slots` (the all-to-all's slots over every worker and
        round, padding included; each worker sends every worker one
        block of `block_capacity` slots a round) and `exchange_records`
        (the records those slots carried, from the returned counts).
        """
        span = timeline.span if timeline is not None else _untimed
        w, rounds = self.w, self.plan.num_rounds
        slots = rounds * w * w * self.cfg.block_capacity(
            keys.shape[0] // (w * rounds))
        with span("map.device_sort", worker=tag):
            with span("map.device_lock", worker=tag):
                self._device_lock.acquire()
            try:
                with span("map.h2d", worker=tag):
                    dk, di = jax.block_until_ready(
                        jax.device_put((keys, ids), self._sharding))
                with span("map.sort", worker=tag, mesh_workers=w,
                          exchange_slots=slots) as attrs:
                    sk, si, vcounts, ovf = jax.block_until_ready(
                        self._sort(dk, di))
                    vcounts = np.asarray(vcounts)  # W counts, no sort work
                    attrs["exchange_records"] = int(vcounts.sum())
                with span("map.d2h", worker=tag):
                    sk, si = np.asarray(sk), np.asarray(si)
            finally:
                self._device_lock.release()
            if bool(np.asarray(ovf)):
                raise RuntimeError(
                    "shuffle block overflow — raise capacity_factor"
                )
        return sk, si, vcounts

    def encode_and_spill(self, store: StoreBackend, bucket: str, g: int,
                         sk, si, vcounts, rows, *,
                         spiller: staging.AsyncWriter,
                         timeline: PhaseTimeline, tag: str,
                         offsets_out: dict, span: str = "map.encode") -> None:
        """Stage 2 of the map body: slice each mesh worker's run out of
        the sorted wave and spill it, its body gathered whole from the
        wave's row buffer `rows` (load_wave's).

        A spilled run is the header plus the wave's rows in sorted order,
        so each run is built with one gather of whole rows straight into
        the spill object's buffer; the bytes equal
        `encode_records(run keys, run ids, payload rows)`.

        Writes per-reducer offsets for every spilled run into
        `offsets_out[(g, wid)]` (they are also persisted in the spill
        object's manifest metadata, so a process-backed worker could
        recover them from the store alone). `span` names the recorded
        compute segments — "map.encode" as a pipeline stage,
        "map.compute" from the monolithic compute_and_spill — which
        leave out each run's map.spill_wait. Inside them, map.pack times
        the spill object's allocation and header, and map.gather the
        id -> row lookup and the whole-row gather into the object; each
        run's map.gather carries `lookup`, "offset" or "argsort".
        """
        plan, w, pw = self.plan, self.w, self.pw
        ids = rows[:, 1]
        with timeline.span(span, worker=tag), \
                timeline.span("map.gather", worker=tag):
            # id -> wave row of each shuffled record: O(1) offset
            # arithmetic when the wave's ids are contiguous (the gensort
            # layout), argsort lookup otherwise.
            id_base = _contiguous_id_base(ids)
            order = sorted_ids = None
            if id_base is None:
                order = np.argsort(ids)
                sorted_ids = ids[order]
        lookup = "argsort" if id_base is None else "offset"
        seg = sk.shape[0] // w
        for wid in range(w):
            with timeline.span(span, worker=tag):
                n = int(vcounts[wid])
                run_k = sk[wid * seg : wid * seg + n]
                run_i = si[wid * seg : wid * seg + n]
                with timeline.span("map.pack", worker=tag):
                    data = np.empty(rec.HEADER_BYTES + n * plan.record_bytes,
                                    np.uint8)
                    data[:rec.HEADER_BYTES] = np.frombuffer(
                        rec.encode_header(n, pw), np.uint8)
                with timeline.span("map.gather", worker=tag, lookup=lookup):
                    if id_base is not None:
                        sel = run_i.astype(np.int64) - id_base
                    else:
                        sel = order[np.searchsorted(sorted_ids, run_i)]
                    # mode="clip" writes straight into `out` ("raise"
                    # would buffer the whole run first).
                    np.take(rows, sel, axis=0, mode="clip",
                            out=data[rec.HEADER_BYTES:].view("<u4")
                            .reshape(n, 2 + pw))
                if self._local_bounds is not None:
                    internal = np.searchsorted(
                        run_k, self._local_bounds[wid], side="left")
                else:
                    internal = np.empty((0,), np.int64)
                offsets = np.concatenate(([0], internal, [n])).astype(np.int64)
                offsets_out[(g, wid)] = offsets
            # Submit each encoded run immediately: the AsyncWriter
            # backpressure bound (at most max_inflight encoded runs
            # in host memory) only holds if we never batch them.
            with timeline.span("map.spill_wait", worker=tag):
                spiller.submit(_timed_spill, timeline, tag, store, bucket,
                               _spill_key(plan, g, wid), data, {
                                   "records": n,
                                   "wave": g,
                                   "worker": wid,
                                   "reducer_offsets": [int(o) for o in offsets],
                               })

    def compute_and_spill(self, store: StoreBackend, bucket: str, g: int,
                          rows, *, spiller: staging.AsyncWriter,
                          timeline: PhaseTimeline, tag: str,
                          offsets_out: dict) -> None:
        """Sort wave g (load_wave's `rows`) on the mesh and spill each
        mesh-worker's run — the monolithic (non-pipelined) map body:
        device_sort + encode_and_spill back to back on the calling
        thread, with the original map.compute/map.spill_wait span
        structure."""
        with timeline.span("map.compute", worker=tag):
            sk, si, vcounts = self.device_sort(rows[:, 0], rows[:, 1])
        self.encode_and_spill(store, bucket, g, sk, si, vcounts, rows,
                              spiller=spiller, timeline=timeline, tag=tag,
                              offsets_out=offsets_out, span="map.compute")


def external_sort(
    store: StoreBackend,
    bucket: str,
    *,
    mesh: jax.sharding.Mesh,
    axis_names: Sequence[str] | str,
    plan: ExternalSortPlan,
    tracer=None,
) -> ExternalSortReport:
    """Sort every record under plan.input_prefix into plan.output_prefix.

    DEPRECATED shim (kept byte- and etag-identical to the pre-refactor
    driver): build the job through the library instead —

        from repro.shuffle.sort import sort_shuffle_job
        sort_shuffle_job(store, bucket, mesh=mesh, axis_names=axis_names,
                         plan=plan).run(workers=0)

    `store` is any io/backends.StoreBackend — the plain ObjectStore, a
    fault-injected middleware stack, or a TieredStore (in which case the
    report carries per-tier request deltas). Input objects must be
    io/records-encoded with plan.payload_words words of payload and
    globally unique ids (data/gensort.write_to_store's layout). Returns
    the run report; validate the output with data/valsort.validate_from_store.
    """
    warnings.warn(
        "external_sort() is a deprecated shim; use "
        "repro.shuffle.sort.sort_shuffle_job(...).run(workers=0)",
        DeprecationWarning, stacklevel=2)
    from repro.shuffle.sort import sort_shuffle_job

    return sort_shuffle_job(store, bucket, mesh=mesh, axis_names=axis_names,
                            plan=plan, tracer=tracer).run(workers=0)
