"""Model zoo package.

``ModelConfig`` / ``get_config`` / ``list_archs`` are pure-Python (config
dataclasses + registry) and import eagerly. The jax-backed model functions
(``forward``, ``init_params``, ...) load lazily on first attribute access
(PEP 562) so that config-only consumers — notably the simulator-side
workload compiler (``repro.core.workload``), which turns ``ModelConfig``s
into gradient traffic — never pull jax into the process.
"""
from .config import ModelConfig
from .registry import get_config, list_archs

_LAZY_TRANSFORMER = ("decode_step", "forward", "init_cache", "init_params",
                     "layer_period", "prepare_cross_cache")

__all__ = ["ModelConfig", "decode_step", "forward", "get_config",
           "init_cache", "init_params", "layer_period", "list_archs",
           "prepare_cross_cache"]


def __getattr__(name: str):
    if name in _LAZY_TRANSFORMER:
        from . import transformer
        return getattr(transformer, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
