"""Per step, the collective ops under the program's ``grad_sync`` scope
that ran on a chip, an asynchronous one counted once by its ``-done``
(``bench.phases``), averaged over the cell's chips."""
from bench import phases


def read(run):
    r = phases.of(run)
    if r is None or "grad_sync" not in r.ns:
        return None
    return r.collectives.get("grad_sync", 0.0)
