"""Cells of the benchmark at a size the CPU runs in seconds: the real
cell's family, traffic shape and limits, with small widths."""
import argparse
import io
import json
import pkgutil
import time
from contextlib import redirect_stdout

from bench import reference, spec
from bench.reference import family

# Each family's ``TINY`` model by family name, for tests outside the
# benchmark that build a small model of a family; found, not listed.
MODELS = {m.name: family(m.name).TINY
          for m in pkgutil.iter_modules(reference.__path__)
          if hasattr(family(m.name), "TINY")}


def cell(name: str, chips: int = None) -> spec.Cell:
    """Cell ``name`` of the benchmark, its model cut to its family's
    ``TINY`` size and its sequences to 128 tokens."""
    real = spec.cell(name)
    config = dict(real.config, model=family(real.config["reference"]).TINY)
    workload = dict(real.workload, seq_len=128)
    return spec.Cell(name=name, chips=chips or real.chips, config=config,
                     workload=workload, limits=real.limits,
                     end_to_end=real.end_to_end, per_layer=real.per_layer)


def run(c: spec.Cell, seed: int = 2 ** 31 + 11) -> dict:
    """A whole run of ``c`` on the CPU, the look for a chip skipped; the
    result line as a dict."""
    import jax
    from bench import harness
    devices = jax.devices()
    peaks = {devices[0].device_kind: {"bf16_flops_per_s": 1e12}}
    args = argparse.Namespace(workload=c.name, seed=seed, seconds=0.5,
                              trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert harness.run(c, args, devices, peaks, time.perf_counter(),
                           0.0) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])
