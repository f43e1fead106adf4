"""map_gather_s_per_GB: the map's payload gather (the id -> row lookup and
the random row gather of each sorted run's 92-byte payloads), as the
program's map.gather span seconds per input GB. The span nests in
map.encode. None where the program records no map.gather span."""


def read(run):
    if not any(ev["name"] == "map.gather"
               for job in run.jobs for ev in job.events):
        return None
    return run.span_seconds(("map.gather",)) / (run.input_bytes / 1e9)
