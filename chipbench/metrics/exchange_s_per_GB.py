"""exchange_s_per_GB: seconds of the mesh exchange per input GB, from the
device trace. Per TPU chip, the time in which an all-to-all ran (a single
op, or an async pair from its start to its done); the chip with the most
sets the number, since the exchange ends when its slowest member does.
0 where the trace holds TPU chips and none ran an all-to-all (a one-chip
mesh exchanges nothing); None without a trace (harness/exchange.py)."""

from harness import exchange


def read(run):
    seconds = exchange.traced_chip_seconds(run)
    if seconds is None:
        return None
    return max(seconds.values()) / (run.input_bytes / 1e9)
