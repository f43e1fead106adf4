"""Input generator: sort-benchmark records made from `--seed`, on the device.

A copy of the record layout and hashes of gensort as the program ports it
(splitmix32 uniform keys, payload words derived from the record id, and the
order-independent (sum, xor) checksum), kept with the benchmark so that no
change to the program can move what it is fed.

Record row, little-endian uint32 words: [key, id, payload x payload_words].
An object is a 16-byte header (magic, version, records, payload_words)
followed by its rows: the program's input format.

The seed changes the data and never the work: it picks where one contiguous
range of ids starts (never wrapping past 2^32). The amount of data, the object
layout and the key distribution are the same for every seed.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

MAGIC = 0x58535254  # "XSRT"
VERSION = 1
HEADER_BYTES = 16
SALT = 0x9E3779B9
#: Key distributions a traffic mix may name in its "keys" field.
KEY_VARIANTS = ("uniform",)

_M64 = (1 << 64) - 1


def header(n_records: int, payload_words: int) -> bytes:
    return np.array([MAGIC, VERSION, n_records, payload_words],
                    dtype="<u4").tobytes()


def first_id(seed: int, total_records: int) -> int:
    """The run's first record id, from splitmix64 of the seed. The ids
    [first, first + total) stay below 2^32 for every seed."""
    z = (int(seed) + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    z ^= z >> 31
    span = (1 << 32) - int(total_records) + 1
    if span < 1:
        raise ValueError(f"{total_records} records do not fit 32-bit ids")
    return z % span


def splitmix32(x):
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def keys_for(ids, variant: str):
    """Key of each record id: gensort's uniform key."""
    if variant not in KEY_VARIANTS:
        raise ValueError(f"keys={variant!r}: must be one of {KEY_VARIANTS}")
    return splitmix32(ids)


def record_hashes(rows):
    """(n,) uint32 hash of each (n, 2 + pw) row over key, id and payload."""
    keys, ids, payload = rows[:, 0], rows[:, 1], rows[:, 2:]
    h = splitmix32(keys ^ splitmix32(ids))
    if payload.shape[1]:
        j = jnp.arange(payload.shape[1], dtype=jnp.uint32)[None, :]
        ph = jnp.sum(splitmix32(payload + j), axis=-1, dtype=jnp.uint32)
        h = splitmix32(h ^ ph)
    return h


def _fold(h, valid):
    h = jnp.where(valid, h, jnp.uint32(0))
    s = jnp.sum(h, dtype=jnp.uint32)
    x = jax.lax.reduce(h, jnp.uint32(0), jax.lax.bitwise_xor, (0,))
    return s, x


def payload_for(ids, payload_words: int):
    """(n, payload_words) payload rows, derived from the ids alone."""
    j = jnp.arange(payload_words, dtype=jnp.uint32)[None, :]
    return splitmix32(ids[:, None] * jnp.uint32(payload_words) + j
                      + jnp.uint32(SALT))


@functools.partial(jax.jit, static_argnames=("n", "payload_words", "variant"))
def gen_block(start, *, n: int, payload_words: int, variant: str):
    """Rows of ids [start, start + n), flat (one linear array copies to the
    host fastest), and their (sum, xor) checksum."""
    ids = jnp.asarray(start, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    keys = keys_for(ids, variant)
    rows = jnp.concatenate([keys[:, None], ids[:, None],
                            payload_for(ids, payload_words)], axis=1)
    return rows.reshape(-1), _fold(record_hashes(rows), jnp.ones((n,), bool))


@functools.partial(jax.jit, static_argnames=("payload_words",))
def check_block(flat, expected_ids, n_valid, *, payload_words: int):
    """For a flat block of output rows (padded past n_valid): which valid
    rows carry another payload than their expected id's, and the (sum, xor)
    checksum of the valid rows."""
    rows = flat.reshape(-1, 2 + payload_words)
    valid = jnp.arange(rows.shape[0]) < n_valid
    bad = jnp.any(rows[:, 2:] != payload_for(expected_ids, payload_words),
                  axis=1) & valid
    return bad, _fold(record_hashes(rows), valid)


def combine(a: tuple[int, int], b: tuple[int, int]) -> tuple[int, int]:
    return (int(a[0]) + int(b[0])) & 0xFFFFFFFF, int(a[1]) ^ int(b[1])


def object_rows(data: bytes, payload_words: int) -> np.ndarray:
    """(n, 2 + pw) row view of one encoded object; ValueError if its header
    is not the record format's or disagrees with its size."""
    magic, version, n, pw = np.frombuffer(data[:HEADER_BYTES], "<u4")
    if (magic, version, pw) != (MAGIC, VERSION, payload_words):
        raise ValueError(f"not a record object of {payload_words} payload "
                         "words")
    body = np.frombuffer(data, "<u4", offset=HEADER_BYTES)
    if body.size != int(n) * (2 + payload_words):
        raise ValueError("object size disagrees with its header")
    return body.reshape(int(n), 2 + payload_words)


@dataclasses.dataclass
class Input:
    """One run's input: the objects as put into the store, in id order."""

    first_id: int
    payload_words: int
    objects: list[tuple[str, bytes]]  # (key, encoded object)
    checksum: tuple[int, int]

    def rows(self, p: int) -> np.ndarray:
        return object_rows(self.objects[p][1], self.payload_words)

    @property
    def total_records(self) -> int:
        return sum(self.rows(p).shape[0] for p in range(len(self.objects)))

    def all_rows(self) -> np.ndarray:
        """Every row in id order, as one new array."""
        return np.concatenate([self.rows(p)
                               for p in range(len(self.objects))])


def make_input(*, total_records: int, records_per_object: int,
               payload_words: int, variant: str, seed: int,
               prefix: str) -> Input:
    """Generate the run's input on the default device, object by object."""
    if total_records % records_per_object:
        raise ValueError("total_records must be a multiple of "
                         "records_per_object")
    first = first_id(seed, total_records)
    objects = []
    ck = (0, 0)
    head = header(records_per_object, payload_words)
    for p, lo in enumerate(range(0, total_records, records_per_object)):
        flat, (s, x) = gen_block(
            np.uint32(first + lo), n=records_per_object,
            payload_words=payload_words, variant=variant)
        body = np.asarray(flat)
        objects.append((f"{prefix}part-{p:05d}",
                        b"".join((head, memoryview(body)))))
        ck = combine(ck, (int(s), int(x)))
    return Input(first_id=first, payload_words=payload_words,
                 objects=objects, checksum=ck)
