"""Per step, the device time of the ops under the program's ``forward``
scope and under no ``transpose(``: the forward pass and the loss
(``bench.phases``), in ms, averaged over the cell's chips."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "forward")
