"""CloudSort as a ShuffleJob: the sort, re-expressed as one instantiation.

The paper's 100 TB sort is, in library terms, nothing special: a MapOp
that loads a wave of input objects and mesh-sorts it into range-
partitioned spill runs (wrapping core/external_sort.WaveSorter — the
device kernels, zero-copy load, and spill layout are unchanged), and a
ReduceOp whose PartitionReducer is a pure streaming k-way merge of
whole records (the same order and bytes as core/external_sort's reduce).
Output bytes are byte- and etag-identical to the pre-refactor drivers at
any parallelism, worker count, and under worker kills — asserted by
tests/test_cluster.py and tests/test_shuffle.py.

The partitioner is the order-preserving RangePartitioner whose equal
boundaries reproduce core/keyspace.KeySpace's reducer boundaries
bit-for-bit; the actual map-side routing runs on the device inside
streaming_sort, and the test suite pins the two constructions together
so they can never drift.
"""
from __future__ import annotations

import contextlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.io import records as rec
from repro.io.backends import StoreBackend

from repro.shuffle.api import MapOp, PartitionReducer, ReduceOp
from repro.shuffle.job import ShuffleJob
from repro.shuffle.partition import RangePartitioner
from repro.shuffle.runtime import merge_order


class SortMapOp(MapOp):
    """Map side of CloudSort: load one wave zero-copy, sort it across
    the device mesh, spill one range-partitioned run per mesh worker
    (with per-reducer offsets in the spill metadata)."""

    def __init__(self, plan, mesh, axis_names, boundaries=None):
        from repro.core import external_sort as xs

        self.plan = plan
        self.sorter = xs.WaveSorter(plan, mesh, axis_names,
                                    boundaries=boundaries)
        self.num_mesh_workers = self.sorter.w
        self.spill_objects_per_task = self.sorter.w
        self.spill_offsets: dict[tuple[int, int], np.ndarray] = {}
        self.waves: list = []

    def plan_tasks(self, store: StoreBackend, bucket: str) -> int:
        from repro.core import external_sort as xs

        plan = self.plan
        inputs = store.list_objects(bucket, plan.input_prefix)
        if not inputs:
            raise ValueError(
                f"input_prefix={plan.input_prefix!r}: no input objects")
        counts = [(m.size - rec.HEADER_BYTES) // plan.record_bytes
                  for m in inputs]
        self.waves = xs._group_waves(inputs, counts, plan.records_per_wave)
        self.total_records = sum(counts)
        self.working_set_records = plan.records_per_wave
        return len(self.waves)

    def load(self, store: StoreBackend, bucket: str, task: int):
        return self.sorter.load_wave(store, bucket, self.waves[task])

    def spill_keys(self, task: int) -> list[str]:
        from repro.core import external_sort as xs

        return [xs._spill_key(self.plan, task, wid)
                for wid in range(self.sorter.w)]

    def process(self, store: StoreBackend, bucket: str, task: int, data, *,
                spiller, timeline, tag) -> None:
        self.sorter.compute_and_spill(
            store, bucket, task, data, spiller=spiller, timeline=timeline,
            tag=tag, offsets_out=self.spill_offsets)

    # Staged map interface (shuffle/runtime's pipelined executor, active
    # when plan.map_pipeline is true): the same body as process(), split
    # at the device boundary so wave N's sort overlaps wave N-1's encode.
    # `data` is load()'s interleaved row buffer.
    def device_step(self, task: int, data, *, timeline, tag):
        return data, self.sorter.device_sort(data[:, 0], data[:, 1],
                                             timeline=timeline, tag=tag)

    def encode_step(self, store: StoreBackend, bucket: str, task: int,
                    staged, *, spiller, timeline, tag) -> None:
        rows, (sk, si, vcounts) = staged
        self.sorter.encode_and_spill(
            store, bucket, task, sk, si, vcounts, rows,
            spiller=spiller, timeline=timeline, tag=tag,
            offsets_out=self.spill_offsets)


class _SortMergeSink(PartitionReducer):
    """Streaming k-way merge: the record count is known up front (sum of
    run-slice lengths), so the header streams first and sorted body
    chunks follow — exactly the monolithic reduce body, hence exactly
    its bytes.

    The merge keeps records whole: each cycle's merged rows land in one
    window buffer, reused across cycles, never split into columns."""

    deferred_part0 = False
    merge = "concat"  # until a cycle interleaves

    def __init__(self, n_total: int, payload_words: int):
        self._n = int(n_total)
        self._pw = int(payload_words)
        self._joined = self._window = rec.alloc_rows(0, self._pw)

    def begin(self) -> bytes:
        return rec.encode_header(self._n, self._pw)

    def consume(self, frags, *, final: bool):
        """The cycle's merged rows, as a bytes view valid until the next
        call. `merge` "concat" when the live fragments do not interleave
        (their rows, in fragment order); "rows" otherwise: one stable
        argsort of the packed keys (ties keep fragment order) and one
        whole-row take from the fragments' rows laid end to end."""
        frags = [f for f in frags if f[3].size]
        order = merge_order([f[3] for f in frags])
        self.merge = "concat" if order is None else "rows"
        if not frags:
            return b""
        if len(frags) == 1:
            return memoryview(frags[0][4]).cast("B")
        n = sum(f[3].size for f in frags)
        if self._window.shape[0] < n:
            self._joined = rec.alloc_rows(n, self._pw)
            self._window = rec.alloc_rows(n, self._pw)
        window = self._window[:n]
        if order is None:
            np.concatenate([f[4] for f in frags], out=window)
        else:
            joined = self._joined[:n]
            np.concatenate([f[4] for f in frags], out=joined)
            np.take(joined, order, axis=0, mode="clip", out=window)
        return memoryview(window).cast("B")


class _DeviceMergeSink(PartitionReducer):
    """Device-resident k-way merge, double-buffered.

    Same byte STREAM as _SortMergeSink, shifted one cycle: consume()
    hands the emit window to a one-thread merge+encode stage
    (kernels/kway_merge.merge_fragments_device — bit-identical to the
    numpy merge, see that module's docstring) and returns the PREVIOUS
    window's encoded bytes, so window i's merge overlaps window i+1's
    ranged-GET fetches; finalize() flushes the last window. Because the
    scheduler slices parts from the concatenated stream at fixed record
    boundaries, parts and etags are identical to the numpy backend at
    any parallelism — pinned by tests/test_device_merge.py.

    Memory: one extra in-flight window (<= runs x chunk decoded bytes)
    rides on top of the budget governor's per-reducer accounting while
    the stage thread drains it.

    A merge failure surfaces on the next consume()/finalize() — the
    scheduler's normal error path (abort the multipart session, retire
    the grant). The stage pool is shut down on finalize and on the first
    error; a reducer abandoned mid-stream (worker death elsewhere)
    releases its idle thread when the sink is collected.
    """

    deferred_part0 = False

    def __init__(self, n_total: int, payload_words: int, *,
                 impl: str = "pallas"):
        self._n = int(n_total)
        self._pw = int(payload_words)
        self._impl = impl
        self._timeline = None
        self._tag = ""
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="device-merge")
        self._pending = None

    def bind_exec(self, *, timeline, tag: str) -> None:
        # Optional sink hook the ReduceScheduler calls right after
        # open(): stage-thread work records reduce.device_merge spans
        # with this partition's tag.
        self._timeline = timeline
        self._tag = tag

    def begin(self) -> bytes:
        return rec.encode_header(self._n, self._pw)

    def _merge_encode(self, frags) -> bytes:
        from repro.kernels.kway_merge import merge_fragments_device

        span = (contextlib.nullcontext() if self._timeline is None else
                self._timeline.span("reduce.device_merge", worker=self._tag))
        with span:
            mk, mi, mp = merge_fragments_device(frags, self._pw,
                                                impl=self._impl)
            return rec.encode_body(mk, mi, mp) if mk.size else b""

    def consume(self, frags, *, final: bool) -> bytes:
        job = self._pool.submit(self._merge_encode, frags)
        prev, self._pending = self._pending, job
        if prev is None:
            return b""
        try:
            return prev.result()
        except BaseException:
            self._pool.shutdown(wait=False)
            raise

    def finalize(self):
        try:
            tail = b"" if self._pending is None else self._pending.result()
        finally:
            self._pool.shutdown(wait=True)
        return tail, None


class MergeReduceOp(ReduceOp):
    """Reduce side of CloudSort: partition r streams its slice of every
    spilled run (located by the offsets the map side recorded) through
    a k-way merge into a multipart-uploaded output partition."""

    def __init__(self, plan, map_op: SortMapOp):
        self.plan = plan
        self.map_op = map_op
        self.payload_words = plan.payload_words

    def sources(self, r: int) -> tuple[list[tuple[str, int, int]], int]:
        from repro.core import external_sort as xs

        plan, map_op = self.plan, self.map_op
        wid, j = divmod(r, map_op.sorter.r1)
        slices, n_total = [], 0
        for g in range(len(map_op.waves)):
            offs = map_op.spill_offsets[(g, wid)]
            lo, hi = int(offs[j]), int(offs[j + 1])
            if hi > lo:
                slices.append((xs._spill_key(plan, g, wid), lo, hi))
                n_total += hi - lo
        return slices, n_total

    def output_key(self, r: int) -> str:
        from repro.core import external_sort as xs

        return xs._output_key(self.plan, r)

    def output_metadata(self, r: int, n_total: int) -> dict:
        return {"records": n_total, "reducer": r}

    def open(self, r: int, n_total: int) -> PartitionReducer:
        return _SortMergeSink(n_total, self.payload_words)


class DeviceMergeReduceOp(MergeReduceOp):
    """MergeReduceOp with the device-resident, double-buffered merge
    sink (_DeviceMergeSink) — selected by
    ExternalSortPlan.reduce_merge_impl="device". Sources, output keys,
    chunk sizing (the AdaptiveBudgetGovernor), and output bytes are all
    identical to the numpy backend; only where (and when) the window
    merge runs changes.

    Lowering: plan.impl="ref" selects the CPU reference MAP sorter, but
    for the merge stage the lax.sort oracle it would pick is ~5x slower
    than the tournament network — so "ref" maps to the kernel's "pallas"
    auto-lowering (pallas_call on accelerators, the jit'd network on
    CPU; all three are pinned bit-identical in tests/test_kernels.py).
    An explicit pallas/network plan.impl is honored as-is."""

    def open(self, r: int, n_total: int) -> PartitionReducer:
        impl = "pallas" if self.plan.impl == "ref" else self.plan.impl
        return _DeviceMergeSink(n_total, self.payload_words, impl=impl)


def sort_shuffle_job(store: StoreBackend, bucket: str, *, mesh, axis_names,
                     plan, tracer=None, boundaries=None) -> ShuffleJob:
    """Build the CloudSort ShuffleJob: SortMapOp + MergeReduceOp (or
    DeviceMergeReduceOp, per plan.reduce_merge_impl) over an
    order-preserving range partitioner. `plan` is a
    core/external_sort.ExternalSortPlan; run with
    `job.run(workers=N[, cluster=ClusterPlan(...)])`. `tracer` is an
    optional obs/events.Tracer the run records into (share it with the
    store stack to get request-level child spans). `boundaries` replaces
    the equal key split with W*R1-1 explicit reducer boundaries (the
    sampling pre-pass quantiles — shuffle/job.sample_boundaries); the
    SAME values feed both the host RangePartitioner and the device
    keyspace routing so the two stay bit-consistent."""
    map_op = SortMapOp(plan, mesh, axis_names, boundaries=boundaries)
    if getattr(plan, "reduce_merge_impl", "numpy") == "device":
        reduce_op: MergeReduceOp = DeviceMergeReduceOp(plan, map_op)
    else:
        reduce_op = MergeReduceOp(plan, map_op)
    partitioner = RangePartitioner(map_op.sorter.w * map_op.sorter.r1,
                                   boundaries=boundaries)
    return ShuffleJob(store, bucket, plan=plan, map_op=map_op,
                      reduce_op=reduce_op, partitioner=partitioner,
                      tracer=tracer)


__all__ = ["DeviceMergeReduceOp", "MergeReduceOp", "SortMapOp",
           "sort_shuffle_job"]
