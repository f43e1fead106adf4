"""partition_imbalance: records of the largest output partition over the
mean, for the last job of the window (the slowest reducer sets the job's
end)."""


def read(run):
    counts = run.partition_records
    if not counts or sum(counts) == 0:
        return None
    return max(counts) * len(counts) / sum(counts)
