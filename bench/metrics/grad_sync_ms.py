"""Per step, the device time of the ops under the program's ``grad_sync``
scope: the explicit reduction of the gradients, its collectives, packing
and division, hidden behind other work or not (``bench.phases``), in ms,
averaged over the cell's chips."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "grad_sync")
