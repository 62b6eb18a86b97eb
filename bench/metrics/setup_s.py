"""Seconds from the start of the process to the first timed step: loading,
building, the compile or the cache's load, and the compared steps."""


def read(run):
    return run.setup_s
