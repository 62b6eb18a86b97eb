"""Per step, the device time of the ops under the program's ``optimizer``
scope: AdamW's update with its clip norm (``bench.phases``), in ms,
averaged over the cell's chips."""
from bench import phases


def read(run):
    return phases.phase_ms(run, "optimizer")
