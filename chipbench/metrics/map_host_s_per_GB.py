"""map_host_s_per_GB: the map's host work (wave decode, payload gather and
spill encode), as the program's map.decode + map.encode span seconds per
input GB."""


def read(run):
    return run.span_seconds(("map.decode", "map.encode")) / (run.input_bytes / 1e9)
