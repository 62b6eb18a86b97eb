"""Plain references of the benchmark's model families.

A configuration file names its family under ``reference``; the module
``bench/reference/<family>.py`` is all that the harness knows of the
family. Each gives:

- ``init(model, key)``: the weights of a configuration in the layout the
  program trains, in float32;
- ``served_dtypes(model)``: the type each weight is served in, to which a
  user rounds them;
- ``loss(params, tokens, labels, model, mm)``: the mean next-token
  cross-entropy, a mean of per-row terms (``bench.check``), in
  straightforward ``jax.numpy`` with every product through ``mm``;
- ``macs_per_token(model, seq)``: the forward multiply-adds per token
  that ``bench.flops`` counts;
- ``TINY``: the model dict of a configuration of the family at a size the
  CPU tests run in seconds (``bench.tests.tiny``).

Nothing here imports the program.
"""
import importlib


def family(name: str):
    """The reference module a configuration names."""
    return importlib.import_module(f"bench.reference.{name}")
