"""Per step, the time in which a collective op (collective-permute,
all-reduce, all-gather, reduce-scatter, all-to-all) runs on a chip and no
other op does, in ms, averaged over the cell's chips."""
from bench import trace


def read(run):
    ws = list(run.windows.values())
    if not ws:
        return None
    per_step = [trace.exposed_collective_ns(w) / len(w.steps) for w in ws]
    return sum(per_step) / len(per_step) / 1e6
