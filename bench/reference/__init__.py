"""Plain references of the benchmark's model families.

Each module gives ``init(model, key)``, the weights of a configuration in
the layout the program trains, in float32 (``served_dtypes(model)`` gives
the type each is served in, to which a user rounds them), and ``loss(params, tokens,
labels, model, mm)``, the mean next-token cross-entropy, in straightforward
``jax.numpy`` with every product through ``mm``. Nothing here imports the
program.
"""
import importlib


def family(name: str):
    """The reference module a configuration names."""
    return importlib.import_module(f"bench.reference.{name}")
