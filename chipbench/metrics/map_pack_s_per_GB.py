"""map_pack_s_per_GB: the map's record encode (header and interleaved body
of each sorted run, built as new bytes), as the program's map.pack span
seconds per input GB. The span nests in map.encode. None where the program
records no map.pack span."""


def read(run):
    if not any(ev["name"] == "map.pack"
               for job in run.jobs for ev in job.events):
        return None
    return run.span_seconds(("map.pack",)) / (run.input_bytes / 1e9)
