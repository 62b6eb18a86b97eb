"""Per step, the device time of the backward ops under remat's
``rematted_computation``: the forward work done again to save memory, a
part of ``backward_ms`` (``bench.phases``), in ms, averaged over the
cell's chips."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "recompute")
