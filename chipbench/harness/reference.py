"""The plain reference and the comparison that decides `correct`.

The reference is a straightforward sort of the generated input by (key, id),
with each record's payload carried by its id. It imports nothing of the
program. The comparison reads the output objects back in key order and holds
them to the sort benchmark's guarantees, every record of every partition:

  records_missing    |input records - output records|
  records_differing  output positions whose (key, id, payload) differs
                     from the reference's record at that position
  order_breaks       adjacent output records out of (key, id) order,
                     within and across partitions (valsort's rule)
  checksum_differs   1 when the output's (sum, xor) record checksum
                     differs from the input's (valsort's rule)
  etags_differing    jobs in the window whose output etags differ from
                     the last job's (whose records are the ones compared)
  jobs_failed        jobs in the window that raised

Each is an exact count, so each limit is 0.
"""
from __future__ import annotations

import numpy as np

from harness import gen

LIMITS = {
    "records_missing": 0,
    "records_differing": 0,
    "order_breaks": 0,
    "checksum_differs": 0,
    "etags_differing": 0,
    "jobs_failed": 0,
}

#: The numbers that compare the last job's records.
RECORD_CHECKS = ("records_missing", "records_differing", "order_breaks",
                 "checksum_differs")

#: Rows compared (and checked on the device) per block.
BLOCK = 1 << 20


def reference_order(inp: gen.Input) -> np.ndarray:
    """The input's (key << 32 | id) values, sorted: the reference output's
    keys and ids. Each record's payload is its id's (gen.payload_for)."""
    k64 = np.empty(inp.total_records, np.uint64)
    lo = 0
    for p in range(len(inp.objects)):
        rows = inp.rows(p)
        part = k64[lo:lo + rows.shape[0]]
        part[:] = rows[:, 0]
        part <<= np.uint64(32)
        part |= rows[:, 1]
        lo += rows.shape[0]
    k64.sort()
    return k64


def compare(inp: gen.Input, outputs, *, etags_differing: int = 0,
            jobs_failed: int = 0) -> dict[str, int]:
    """Hold `outputs` (iterable of encoded objects, in key order) to the
    reference sort of `inp`. Keys and ids are compared on the host; each
    payload with its expected id's, and the checksum, on the device."""
    ref = reference_order(inp)
    pw = inp.payload_words
    total = ref.size
    pos = 0
    differing = 0
    breaks = 0
    prev = None
    ck = (0, 0)
    pad = np.zeros(BLOCK * (2 + pw), "<u4")
    pad_ids = np.zeros(BLOCK, "<u4")
    for data in outputs:
        rows = gen.object_rows(data, pw)
        for lo in range(0, rows.shape[0], BLOCK):
            got = rows[lo:lo + BLOCK]
            n = got.shape[0]
            k64 = got[:, 0].astype(np.uint64) << np.uint64(32)
            k64 |= got[:, 1]
            breaks += int(np.count_nonzero(k64[1:] < k64[:-1]))
            if prev is not None and n and k64[0] < prev:
                breaks += 1
            if n:
                prev = k64[-1]
            m = max(0, min(n, total - pos))
            exp = ref[pos:pos + m]
            pad[:n * (2 + pw)] = got.reshape(-1)
            pad_ids[:m] = exp & np.uint64(0xFFFFFFFF)
            bad, (s, x) = gen.check_block(pad, pad_ids, np.int32(n),
                                          payload_words=pw)
            bad = np.asarray(bad)[:m] | (k64[:m] != exp)
            differing += int(np.count_nonzero(bad))
            ck = gen.combine(ck, (int(s), int(x)))
            pos += n
    return {
        "records_missing": abs(total - pos),
        "records_differing": differing,
        "order_breaks": breaks,
        "checksum_differs": int(ck != tuple(inp.checksum)),
        "etags_differing": int(etags_differing),
        "jobs_failed": int(jobs_failed),
    }


def passes(numbers: dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def control_outputs(inp: gen.Input, *, records_per_wave: int,
                    partitions: int) -> list[bytes]:
    """The control: the reference put in the program's place with one
    guarantee broken. Each wave is sorted and range-partitioned on its
    own, and each partition concatenates its slices of the waves without
    merging them: the cross-run merge left out, so a partition holds
    several sorted runs instead of one sorted run."""
    pw = inp.payload_words
    rows = inp.all_rows()
    bounds = (np.arange(1, partitions, dtype=np.uint64)
              * np.uint64(1 << 32)) // np.uint64(partitions)
    slices: list[list[np.ndarray]] = [[] for _ in range(partitions)]
    for lo in range(0, rows.shape[0], records_per_wave):
        wave = rows[lo:lo + records_per_wave]
        k64 = wave[:, 0].astype(np.uint64) << np.uint64(32)
        k64 |= wave[:, 1]
        srt = wave[np.argsort(k64, kind="stable")]
        cuts = np.searchsorted(srt[:, 0].astype(np.uint64), bounds)
        for j, part in enumerate(np.split(srt, cuts)):
            slices[j].append(part)
    return [gen.header(sum(p.shape[0] for p in parts), pw)
            + np.concatenate(parts).tobytes() for parts in slices]
