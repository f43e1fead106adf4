"""map_device_sort_s_per_GB: the wave sort as the host sees it (host to
device copy, device-lock wait, the sort, device to host copy), as the
program's map.device_sort span seconds per input GB."""


def read(run):
    return run.span_seconds(("map.device_sort",)) / (run.input_bytes / 1e9)
