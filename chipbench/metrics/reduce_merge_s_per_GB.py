"""reduce_merge_s_per_GB: the reduce's k-way merge, as the program's
reduce.merge span seconds per input GB (spans of concurrent reducers add)."""


def read(run):
    return run.span_seconds(("reduce.merge",)) / (run.input_bytes / 1e9)
