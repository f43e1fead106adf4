"""sort_MBps: input bytes of every job in the window (100 B x records) over
the seconds from the first job's start to the last job's end, in MB/s."""


def read(run):
    return run.input_bytes / 1e6 / run.window_s
