"""exchange_ici_roofline: the mesh exchange's share of its ICI roofline, the
useful bytes each chip sends the other chips over the window's jobs
(harness/exchange.useful_bytes_per_chip: records x 8 B x (W-1)/W, padding
not counted) over that chip's all-to-all seconds in the device trace times
the chip's published ICI bandwidth (tables/ici.json). The chip with the
lowest share is reported. 0 on a one-chip mesh, where no byte crosses
between chips; None without a trace (harness/exchange.py) or where a chip
of the mesh shows no all-to-all."""

from harness import exchange


def read(run):
    seconds = exchange.traced_chip_seconds(run)
    if seconds is None:
        return None
    workers = int(run.config["mesh_workers"])
    useful = exchange.useful_bytes_per_chip(
        len(run.jobs) * run.records_per_job, workers)
    if useful == 0:
        return 0.0
    busy = [s for s in seconds.values() if s > 0]
    if len(busy) < workers:
        return None
    peak = exchange.ici_bytes_per_s(exchange.device_kind())
    return min(100.0 * useful / (s * peak) for s in busy)
