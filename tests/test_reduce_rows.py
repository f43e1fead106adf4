"""The sort's reduce merges whole rows into pooled output part buffers.

`_SortMergeSink.consume` merges each cycle's fragments as whole rows and
the scheduler copies them once into a pooled part buffer. It must write
the same output objects as the column path it replaced: the same bytes,
the same part count and part sizes, the same etag. The reference is that
column path, kept here as `_ColumnSortSink` (merge_fragments, then
encode_body, bytes appended to the part stream). The other sinks
(group-by aggregation, recursive concat, device merge) stay on the
column path and are checked against independent references.

Runs are written straight into the store as spilled runs are, and the
ReduceScheduler merges them; no device and no map phase is involved.
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

from repro.io import records as rec
from repro.io.backends import FilesystemBackend, MemoryBackend
from repro.shuffle import runtime as rt
from repro.shuffle.api import ReduceOp, ShufflePlan
from repro.shuffle.sort import _SortMergeSink


class _ColumnSortSink(_SortMergeSink):
    """The reference: the sort sink's column path (merge_fragments, then
    encode_body), as the reduce ran before it merged whole rows."""

    merge = "columns"

    def consume(self, frags, *, final):
        mk, mi, mp = rt.merge_fragments(frags, self._pw)
        return rec.encode_body(mk, mi, mp) if mk.size else b""


def _recording(store):
    """Wrap `store.multipart` so every put_part's (index, size) is kept."""
    sizes: dict[str, dict[int, int]] = {}
    lock = threading.Lock()
    multipart = store.multipart

    def wrapped(bucket, key, metadata=None):
        mp = multipart(bucket, key, metadata)
        put = mp.put_part

        def put_part(index, data):
            with lock:
                sizes.setdefault(key, {})[index] = len(data)
            put(index, data)

        mp.put_part = put_part
        return mp

    store.multipart = wrapped
    return sizes


def _store(kind: str, tmp_path, tag: str):
    store = (MemoryBackend() if kind == "mem"
             else FilesystemBackend(str(tmp_path / tag)))
    store.create_bucket("b")
    return store


def _run_object(store, key, keys, ids, pw, tag=0):
    """Spill one sorted run (by packed key, stable) as an encoded object.
    Payload word j is id * 7 + j, plus `tag` << 24: a row can be checked
    whole, and equal packed keys of different runs told apart."""
    keys = np.asarray(keys, np.uint32)
    ids = np.asarray(ids, np.uint32)
    k64 = keys.astype(np.uint64) << np.uint64(32) | ids
    order = np.argsort(k64, kind="stable")
    keys, ids = keys[order], ids[order]
    payload = ((ids[:, None] * np.uint32(7) + np.arange(pw, dtype=np.uint32)
                + np.uint32(tag << 24)) if pw else None)
    store.put("b", key, rec.encode_records(keys, ids, payload))
    return keys.size


class _Op(ReduceOp):
    """One output partition per entry of `parts`, each a list of
    (run key, lo, hi) slices; `sink` builds the partition's reducer."""

    def __init__(self, pw, parts, sink, *, sequential=False):
        self.payload_words = pw
        self._parts = parts
        self._sink = sink
        self._sequential = sequential

    def sources(self, r):
        slices = self._parts[r]
        return list(slices), sum(hi - lo for _, lo, hi in slices)

    def output_key(self, r):
        return f"out/{r:03d}"

    def output_metadata(self, r, n_total):
        return {"records": n_total}

    def open(self, r, n_total):
        return self._sink(n_total, self.payload_words)

    def sequential_partition(self, r):
        return self._sequential


def _reduce(store, op, *, chunk_records, part_records, width=2, fanout=2):
    """Run the ReduceScheduler over `op`; returns, per partition, (object
    bytes, etag, part count, {index: size}) and each reduce.merge span's
    `merge` attribute."""
    sizes = _recording(store)
    merges: list[str] = []
    lock = threading.Lock()

    def sink(phase, start, end, worker, attrs):
        if phase == "reduce.merge":
            with lock:
                merges.append(attrs["merge"])

    rb = rec.record_bytes(op.payload_words)
    plan = ShufflePlan(payload_words=op.payload_words,
                       merge_chunk_bytes=chunk_records * rb,
                       output_part_records=part_records,
                       parallel_reducers=width, part_upload_fanout=fanout,
                       max_inflight_writes=1)
    n = len(op._parts)
    shared = rt.ReduceShared(
        plan=plan, bucket="b", reduce_op=op,
        governor=rt.AdaptiveBudgetGovernor(
            budget=0, chunk_cap=plan.merge_chunk_bytes, record_bytes=rb,
            slots=width, partitions=n),
        timeline=rt.PhaseTimeline(sink=sink), peak=rt.PeakTracker(),
        control=rt.JobControl())
    todo = list(range(n))

    def pop():
        with lock:
            return todo.pop(0) if todo else None

    rt.ReduceScheduler(store, shared, width=width).run(pop)
    shared.control.raise_first()
    out = []
    for r in range(n):
        key = op.output_key(r)
        meta = store.head("b", key)
        out.append((store.get("b", key), meta.etag, meta.parts,
                    sizes.get(key, {})))
    return out, merges


# name: (payload_words, records of each run, key range, ids shared by
# every run, chunk_records, output_part_records)
CASES = {
    # one run: every cycle is one fragment, copied through
    "fanin1": (23, [300], 1 << 32, False, 7, 5),
    "fanin2": (23, [250, 190], 1 << 32, False, 7, 5),
    "fanin8": (23, [41, 7, 90, 0, 33, 64, 1, 58], 1 << 32, False, 6, 9),
    # few keys, the same ids in every run: equal packed keys across runs
    "ties": (23, [120, 120, 120], 4, True, 40, 7),
    # run i holds keys [i * 1000, i * 1000 + 999]: the runs never interleave
    "disjoint": (23, [80, 60, 70], None, False, 9, 4),
    # the whole partition fits in part 0
    "small": (23, [3, 2], 1 << 32, False, 7, 64),
    "empty": (23, [], 1 << 32, False, 7, 5),
    # record widths around the 16-byte header: the row grid of a part
    # starts 0, 4 or 16 bytes in
    "pw0": (0, [97, 88], 1 << 32, False, 11, 5),
    "pw1": (1, [97, 88], 1 << 32, False, 11, 5),
    "pw2": (2, [97, 88], 1 << 32, False, 11, 5),
}


def _sort_case(store, name):
    pw, lens, key_range, shared_ids, _, _ = CASES[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    parts = [[], []]  # two partitions over the same runs, split at half
    for g, n in enumerate(lens):
        if key_range is None:
            keys = g * 1000 + rng.integers(0, 1000, n)
        else:
            keys = rng.integers(0, key_range, n, dtype=np.uint64)
        ids = (rng.integers(0, 50, n) if shared_ids
               else np.arange(n) + 10_000 * g)
        _run_object(store, f"run{g}", keys, ids, pw, tag=g)
        half = n // 2
        parts[0].append((f"run{g}", 0, half))
        parts[1].append((f"run{g}", half, n))
    parts = [[s for s in p if s[2] > s[1]] for p in parts]
    return pw, parts


@pytest.mark.parametrize("kind", ["mem", "fs"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_row_merge_writes_the_column_paths_objects(name, kind, tmp_path):
    pw, _, _, _, chunk, part_records = CASES[name]
    got = {}
    for sink in (_SortMergeSink, _ColumnSortSink):
        store = _store(kind, tmp_path, sink.__name__)
        pw, parts = _sort_case(store, name)
        got[sink] = _reduce(store, _Op(pw, parts, sink),
                            chunk_records=chunk, part_records=part_records)
    (rows_out, rows_merges), (cols_out, cols_merges) = (
        got[_SortMergeSink], got[_ColumnSortSink])
    rb = rec.record_bytes(pw)
    for (data, etag, nparts, sizes), want in zip(rows_out, cols_out):
        assert data == want[0]
        assert (etag, nparts, sizes) == want[1:]
        # the parts are the fixed part_records x rb slices of the stream
        p = part_records * rb
        assert [sizes[i] for i in sorted(sizes)] == (
            [p] * (len(data) // p) + ([len(data) % p] if len(data) % p
                                      else []))
        # the body is the input rows in packed-key order, rows intact
        keys, ids, payload = rec.decode_records(data)
        k64 = keys.astype(np.uint64) << np.uint64(32) | ids
        assert np.all(k64[:-1] <= k64[1:])
        if pw:
            np.testing.assert_array_equal(
                payload & np.uint32(0xFFFFFF), ids[:, None] * np.uint32(7)
                + np.arange(pw, dtype=np.uint32))
    assert set(cols_merges) == {"columns"}
    assert set(rows_merges) <= {"rows", "concat"}
    if name in ("fanin2", "fanin8", "ties", "pw0", "pw1", "pw2"):
        assert "rows" in rows_merges
    if name in ("fanin1", "disjoint", "small", "empty"):
        assert set(rows_merges) == {"concat"}


def test_row_merge_keeps_equal_keys_in_run_order(tmp_path):
    """Every record has the same packed key: each emit cycle takes one
    chunk of every run, and its output is run 0's rows, then run 1's,
    then run 2's."""
    store = _store("mem", tmp_path, "ties")
    for g in range(3):
        _run_object(store, f"run{g}", np.full(40, 9), np.full(40, 4), 23,
                    tag=g)
    parts = [[(f"run{g}", 0, 40) for g in range(3)]]
    (out,), merges = _reduce(store, _Op(23, parts, _SortMergeSink),
                             chunk_records=7, part_records=11)
    _, _, payload = rec.decode_records(out[0])
    np.testing.assert_array_equal(payload[:, 0] >> 24, np.concatenate(
        [np.repeat([0, 1, 2], c) for c in [7, 7, 7, 7, 7, 5]]))
    # equal keys never interleave: every cycle concatenates its fragments
    assert set(merges) == {"concat"}


@pytest.mark.parametrize("part_bytes", [1, 7, 100, 4096])
def test_part_writer_cuts_the_stream_at_fixed_offsets(part_bytes):
    rng = np.random.default_rng(part_bytes)
    chunks = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
              for n in (16, 0, 250, 3, 1, 999, 100)]
    writer = rt.PartWriter(rt.PartPool(part_bytes), first_index=3)
    for c in chunks:
        writer.write(c)
    writer.write(memoryview(np.arange(50, dtype="<u4").reshape(2, 25)))
    last = writer.close()
    parts = [buf.tobytes() for _, buf in writer.full]
    if last is not None:
        parts.append(last[1].tobytes())
    stream = b"".join(chunks) + np.arange(50, dtype="<u4").tobytes()
    assert parts == [stream[i:i + part_bytes]
                     for i in range(0, len(stream), part_bytes)]
    assert [i for i, _ in writer.full] == list(
        range(3, 3 + len(writer.full)))


def test_part_pool_reuses_returned_buffers():
    pool = rt.PartPool(64)
    a = pool.acquire()
    b = pool.acquire()
    assert a is not b and a.nbytes == b.nbytes == 64
    pool.release(a)
    assert pool.acquire() is a


def test_group_by_sink_stays_on_the_column_path(tmp_path):
    """_GroupAggSink: the deferred part-0 header and the per-key counts
    and sums equal a plain aggregation of the input."""
    from repro.shuffle.groupby import _GroupAggSink

    store = _store("mem", tmp_path, "groupby")
    rng = np.random.default_rng(3)
    all_keys, all_vals, slices = [], [], []
    for g in range(3):
        keys = rng.integers(0, 40, 150)
        vals = rng.integers(0, 1000, 150).astype(np.uint32)
        order = np.argsort(keys, kind="stable")
        keys, vals = keys[order].astype(np.uint32), vals[order]
        store.put("b", f"run{g}", rec.encode_records(
            keys, np.ones(150, np.uint32), vals.reshape(-1, 1)))
        all_keys.append(keys)
        all_vals.append(vals)
        slices.append((f"run{g}", 0, 150))
    op = _Op(1, [slices], lambda n, pw: _GroupAggSink(pw))
    (out,), merges = _reduce(store, op, chunk_records=9, part_records=4)
    keys, counts, sums = rec.decode_records(out[0])
    want_keys, inv = np.unique(np.concatenate(all_keys), return_inverse=True)
    np.testing.assert_array_equal(keys, want_keys)
    np.testing.assert_array_equal(counts, np.bincount(inv))
    np.testing.assert_array_equal(
        sums[:, 0], np.bincount(inv, np.concatenate(all_vals)).astype(
            np.uint32))
    assert set(out[3]) == set(range(out[2]))  # part 0 is the header
    assert set(merges) == {"columns"}


def test_concat_sink_stays_on_the_column_path(tmp_path):
    """_ConcatSink (a redirected partition, drained one run at a time):
    the runs' rows in source order, behind the header."""
    from repro.shuffle.recursive import _ConcatSink

    store = _store("fs", tmp_path, "concat")
    want = []
    for g, n in enumerate([70, 0, 45]):
        _run_object(store, f"run{g}", np.arange(n)[::-1] * 3 + g,
                    np.arange(n), 23)
        want.append(store.get("b", f"run{g}")[rec.HEADER_BYTES:])
    slices = [(f"run{g}", 0, n) for g, n in enumerate([70, 0, 45]) if n]
    op = _Op(23, [slices], _ConcatSink, sequential=True)
    (out,), merges = _reduce(store, op, chunk_records=8, part_records=6)
    assert out[0] == rec.encode_header(115, 23) + b"".join(want)
    assert set(merges) == {"columns"}


def test_device_merge_sink_writes_the_sort_sinks_objects(tmp_path):
    """_DeviceMergeSink (the device k-way merge, on the column path)
    writes the same objects as the row path."""
    from repro.shuffle.sort import _DeviceMergeSink

    got = {}
    for sink in (_SortMergeSink, _DeviceMergeSink):
        store = _store("mem", tmp_path, sink.__name__)
        pw, parts = _sort_case(store, "fanin2")
        got[sink] = _reduce(store, _Op(pw, parts, sink), chunk_records=40,
                            part_records=33)
    assert got[_DeviceMergeSink][0] == got[_SortMergeSink][0]
    assert set(got[_DeviceMergeSink][1]) == {"columns"}
