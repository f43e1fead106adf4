"""reduce_partition_time_imbalance: per job, the longest reduce.partition
span (one partition attempt's hold on a reducer slot) over the median one,
averaged over the window's jobs: how far the slowest partition stretches
the reduce's end. None where no job recorded a reduce.partition span."""

import statistics


def read(run):
    ratios = []
    for job in run.jobs:
        durs = [ev["dur"] for ev in job.events
                if ev["name"] == "reduce.partition"]
        if durs and statistics.median(durs) > 0:
            ratios.append(max(durs) / statistics.median(durs))
    if not ratios:
        return None
    return sum(ratios) / len(ratios)
