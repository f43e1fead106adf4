"""setup_s: seconds from the start of the process to the start of the
window: imports, device start, input generation, store fill and the wave
sort's compile or cache load."""


def read(run):
    return run.setup_s
