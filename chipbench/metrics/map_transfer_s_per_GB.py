"""map_transfer_s_per_GB: the wave sort's copies, host to device (keys and
ids, waited for) and device to host (the sorted run), as the program's
map.h2d + map.d2h span seconds per input GB. Both nest in map.device_sort.
None where the program records neither span."""

NAMES = ("map.h2d", "map.d2h")


def read(run):
    if not any(ev["name"] in NAMES for job in run.jobs for ev in job.events):
        return None
    return run.span_seconds(NAMES) / (run.input_bytes / 1e9)
