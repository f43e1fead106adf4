"""The mesh exchange as the device trace shows it: each chip's all-to-all
intervals, read from the profiler's .xplane.pb.

The runner hands metric readers a reduction of the trace that sums ops over
every chip (harness/trace.py). The exchange is judged per chip, so its
readers take the per-chip intervals from the trace file itself, which a
traced run keeps under <checkout>/.chipbench/trace (run.py's work
directory) until its readers have run.

An all-to-all is either one op ("%all_to_all.28 = u32[...] all-to-all(...)")
or an async pair, a start and its done; a pair's interval runs from the
start's beginning to the done's end, starts and dones matched in order on
each chip.

The exchange's work and the chip's peak, for its roofline: the useful bytes
each chip sends the others (`useful_bytes_per_chip`) and the chip's published
ICI bandwidth (tables/ici.json, which refuses an unknown device kind).
"""
from __future__ import annotations

import functools
import os
import re

from harness import tables
from harness import trace as tr

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_DIR = os.path.join(CHECKOUT, ".chipbench", "trace")
DEVICE_PLANE = "/device:TPU:"
#: The HLO opcode of an op event's text after " = ": the first lower-case
#: word after a space that opens a parenthesis. The shape before it has
#: none such, and operands are "%name"s, so neither matches.
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")


def load_planes(path: str) -> dict[str, list[tuple[str, float, float]]]:
    """{TPU plane name: [(op text, start_ns, end_ns)]} of each device
    plane's "XLA Ops" line."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            planes[plane.name] = [
                (ev.name, float(ev.start_ns), float(ev.end_ns))
                for line in plane.lines if line.name == tr.OPS_LINE
                for ev in line.events]
    return planes


def _kind(text: str) -> str | None:
    """"op", "start" or "done" for an all-to-all event, else None."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    if opcode == "all-to-all":
        return "op"
    if opcode in ("all-to-all-start", "all-to-all-done"):
        return opcode.rsplit("-", 1)[1]
    if opcode in ("async-start", "async-done") and re.search(
            r"all[-_]to[-_]all", name):
        return opcode.split("-", 1)[1]
    return None


def all_to_all_intervals(ops) -> list[tuple[float, float]]:
    """(start_ns, end_ns) of every all-to-all among one chip's ops."""
    out, open_starts = [], []
    for text, s, e in sorted(ops, key=lambda op: op[1]):
        kind = _kind(text)
        if kind == "op":
            out.append((s, e))
        elif kind == "start":
            open_starts.append(s)
        elif kind == "done" and open_starts:
            out.append((open_starts.pop(0), e))
    return out


def chip_seconds(planes) -> dict[str, float]:
    """{plane: seconds in which an all-to-all ran on that chip}."""
    return {name: sum(e - s for s, e in tr.union(all_to_all_intervals(ops)))
            / 1e9 for name, ops in planes.items()}


@functools.lru_cache(maxsize=1)
def _trace_chip_seconds(path: str, mtime_ns: int) -> dict[str, float]:
    return chip_seconds(load_planes(path))


def traced_chip_seconds(run) -> dict[str, float] | None:
    """chip_seconds of the run's trace; None for an untraced run, a run
    whose trace is not in TRACE_DIR, or a trace with no TPU plane. The
    readers of one run share one read of the file."""
    if run.device_trace is None:
        return None
    path = tr.find_xplane(TRACE_DIR)
    if path is None:
        return None
    return _trace_chip_seconds(path, os.stat(path).st_mtime_ns) or None


#: Bytes of one record that the exchange moves: its 4-byte key and 4-byte id.
EXCHANGE_RECORD_BYTES = 8


def useful_bytes_per_chip(records: int, workers: int) -> float:
    """Bytes each of `workers` chips must send the others to exchange
    `records` records spread evenly over them, keys and ids alone: a chip
    holds records / W of them and keeps the 1/W whose destination is
    itself. Padding is not work, whatever implements the exchange."""
    return (records / workers * EXCHANGE_RECORD_BYTES
            * (workers - 1) / workers)


def ici_bytes_per_s(device_kind: str, root: str = tables.TABLES) -> float:
    """The chip's published interchip interconnect bandwidth, bytes/s."""
    table = tables._load("ici.json", root)["ici_gbps_per_chip"]
    if device_kind not in table:
        raise tables.UnknownDevice(f"device kind {device_kind!r} is not in "
                                   f"the ICI table ({sorted(table)})")
    return table[device_kind]["gbps"] * 1e9 / 8


def device_kind() -> str:
    """The kind of the chips the run ran on."""
    import jax

    return jax.devices()[0].device_kind
