"""Per chip and step, the bytes that the collectives of the compiled step
return (``bench.hlo``), in MB (1e6 bytes)."""
from bench import hlo


def read(run):
    if run.step_hlo is None:
        return None
    total = sum(hlo.collective_bytes(run.step_hlo()).values())
    return total / 1e6 if total else None
