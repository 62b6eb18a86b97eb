"""Per step, the device time of the ops under the program's
``transpose(jvp(forward))``: the backward pass, remat's recompute included
(``bench.phases``), in ms, averaged over the cell's chips."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "backward")
