"""map_host_offcpu_share: the share of the map's host spans (map.decode and
map.encode) that their threads spent off the CPU (waiting on the GIL, a
lock, I/O or the scheduler), 100 x (1 - summed cpu_s / summed span seconds)
over the spans that carry the thread's CPU seconds (`cpu_s`). None where no
such span was recorded."""

NAMES = ("map.decode", "map.encode")


def read(run):
    wall = cpu = 0.0
    for job in run.jobs:
        for ev in job.events:
            if ev["name"] in NAMES and "cpu_s" in ev:
                wall += ev["dur"]
                cpu += ev["cpu_s"]
    if wall <= 0:
        return None
    return 100.0 * (1.0 - cpu / wall)
