"""Products at a stated precision, and the pieces every family shares.

``products("float32")`` multiplies in float32 at the highest matmul
precision, as a plain reference must on a TPU, where float32 products
otherwise run in bfloat16. ``products("float8_e4m3fn")`` serves the
control: every operand of every product, in the forward and the backward
pass, is rounded to float8 (e4m3) after scaling its largest magnitude to
the format's largest, which is how float8 training keeps its range.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rounded(x: jnp.ndarray, dtype: str) -> jnp.ndarray:
    """``x`` rounded to ``dtype`` and kept in float32. ``reduce_precision``
    and not a round trip through ``astype``: XLA may drop a pair of
    converts that it finds to cancel."""
    if dtype == "float32":
        return x
    if dtype == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if dtype == "float8_e4m3fn":
        return _float8(x)
    raise ValueError(f"no rounding to {dtype!r}")


def _float8(x: jnp.ndarray) -> jnp.ndarray:
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def products(precision: str) -> Callable:
    """``mm(spec, *operands)``: an einsum at ``precision``."""
    def exact(spec, *ops):
        return jnp.einsum(spec, *[o.astype(jnp.float32) for o in ops],
                          precision=HIGHEST)

    if precision == "float32":
        return exact
    if precision != "float8_e4m3fn":
        raise ValueError(f"no products at {precision!r}")

    def low(spec, *ops):
        @jax.custom_vjp
        def f(*o):
            return exact(spec, *[_float8(x) for x in o])

        def fwd(*o):
            q = [_float8(x) for x in o]
            return exact(spec, *q), q

        def bwd(q, g):
            _, vjp = jax.vjp(lambda *o: exact(spec, *o), *q)
            return vjp(_float8(g))

        f.defvjp(fwd, bwd)
        return f(*ops)

    return low


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean negative log-likelihood of ``labels`` under ``logits``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def normal(key, shape, std: float) -> jnp.ndarray:
    return jax.random.normal(key, shape, jnp.float32) * std


def uniform(key, shape, bound: float) -> jnp.ndarray:
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
