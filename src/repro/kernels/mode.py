"""Where the Pallas kernels run: compiled by Mosaic on a TPU, in the Pallas
interpreter on every other backend.

This is the one place that decides. Kernels read it while they are traced,
so nothing else takes an ``interpret`` flag and no path falls back to the
interpreter when a compile fails.

The choice follows the default backend, not the platform a program is
lowered for: on a TPU host, a kernel traced under
``jax.default_device(jax.devices("cpu")[0])`` is still lowered through
Mosaic and fails to compile for the CPU. Run kernels on the default
backend.
"""
from __future__ import annotations

import jax


def interpret() -> bool:
    return jax.default_backend() != "tpu"
