"""Mamba-2 layer via the SSD (state-space duality) chunked algorithm
(arXiv:2405.21060, Listing 1), adapted to JAX with (B, S, H, P) heads.

Training/prefill uses the quadratic-within-chunk + recurrent-across-chunk
formulation; decode uses the O(1) per-token state recurrence. Group count is
fixed at 1 (B/C shared across heads), matching Mamba-2's default.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Params = Dict[str, jnp.ndarray]


def init_mamba2(key, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    n = cfg.ssm_state
    h = cfg.ssm_heads
    conv_ch = di + 2 * n  # x + B + C go through the causal conv
    ks = jax.random.split(key, 6)
    return {
        # in_proj produces [z (di), xBC (di + 2n), dt (h)]
        "w_in": (jax.random.normal(ks[0], (d, 2 * di + 2 * n + h))
                 * d ** -0.5).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch))
                   * cfg.ssm_conv ** -0.5).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype=dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), dtype=jnp.float32),
        "dt_bias": jnp.zeros((h,), dtype=jnp.float32),
        "norm_scale": jnp.ones((di,), dtype=jnp.float32),
        "w_out": (jax.random.normal(ks[2], (di, d)) * di ** -0.5).astype(dtype),
    }


def _chunk_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., T) -> (..., T): prefix sums along the last axis as one
    triangular matmul, which the TPU runs on the MXU (a cumsum lowers to a
    windowed sum on the VPU). HIGHEST keeps the f32 sums f32-accurate: by
    default the TPU would round x to bf16, and the sums reach the hundreds."""
    T = x.shape[-1]
    U = jnp.triu(jnp.ones((T, T), jnp.float32))                  # U[s, l] = s <= l
    return lax.dot_general(x, U, (((x.ndim - 1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _segsum(csum: jnp.ndarray) -> jnp.ndarray:
    """(..., T) prefix sums -> (..., T, T): segment sums, -inf above diagonal."""
    T = csum.shape[-1]
    diff = csum[..., :, None] - csum[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), dtype=bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int,
                init_state=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD forward.

    x:  (b, s, h, p)   head inputs
    dt: (b, s, h)      positive step sizes
    A:  (h,)           negative per-head decay rates
    Bm: (b, s, n)      input projection (group-shared)
    Cm: (b, s, n)      output projection (group-shared)
    Returns (y (b, s, h, p), final_state (b, h, p, n)).
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    assert s % chunk == 0, (s, chunk)
    c = s // chunk
    f32 = jnp.float32
    xd = (x.astype(f32) * dt.astype(f32)[..., None])             # dt-weighted
    dA = dt.astype(f32) * A.astype(f32)[None, None, :]           # (b, s, h)

    # chunked views
    xc = xd.reshape(b, c, chunk, h, p)
    dAc = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)       # (b,h,c,q)
    Bc = Bm.astype(f32).reshape(b, c, chunk, n)
    Cc = Cm.astype(f32).reshape(b, c, chunk, n)

    A_cum = _chunk_cumsum(dAc)                                   # (b,h,c,q)

    # 1) intra-chunk (quadratic) term
    L = jnp.exp(_segsum(A_cum))                                  # (b,h,c,q,q)
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)

    # 2) chunk end-states
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)              # (b,h,c,q)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc)

    # 3) inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(A_cum[..., -1])                        # (b,h,c)

    def step(carry, inp):
        st_in = carry                                            # (b,h,p,n)
        dec, st_chunk = inp                                      # (b,h), (b,h,p,n)
        st_out = st_in * dec[..., None, None] + st_chunk
        return st_out, st_in

    init = jnp.zeros((b, h, p, n), f32) if init_state is None \
        else init_state.astype(f32)
    final_state, states_in = lax.scan(
        step, init,
        (chunk_decay.transpose(2, 0, 1), states.transpose(1, 0, 2, 3, 4)))
    states_in = states_in.transpose(1, 0, 2, 3, 4)               # (b,c,h,p,n)

    # 4) state -> output term
    state_decay = jnp.exp(A_cum)                                 # (b,h,c,q)
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states_in, state_decay)

    y = (Y_diag + Y_off).reshape(b, s, h, p)
    return y, final_state


def mamba2_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig
                   ) -> jnp.ndarray:
    """Full-sequence forward (training / prefill). x: (B, S, d)."""
    B, S, d = x.shape
    di, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    proj = x @ p["w_in"]                                         # (B,S,...)
    z, xBC, dt = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    # causal depthwise conv over (x,B,C)
    w = p["conv_w"]                                              # (K, ch)
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S, :] * w[i][None, None, :] for i in range(K))
    xBC = jax.nn.silu(conv + p["conv_b"])
    xs, Bm, Cm = jnp.split(xBC, [di, di + n], axis=-1)
    xs = xs.reshape(B, S, h, cfg.ssm_head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,h)
    A = -jnp.exp(p["A_log"])                                     # (h,)
    pad = (-S) % cfg.ssm_chunk
    if pad:
        # dt=0 padding is state-neutral: decay exp(0*A)=1, input weight 0
        xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_p = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        C_p = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        y, _ = ssd_chunked(xs_p, dt_p, A, B_p, C_p, cfg.ssm_chunk)
        y = y[:, :S]
    else:
        y, _ = ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, di)
    # gated RMSNorm (Mamba-2)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"]
    return (g.astype(x.dtype)) @ p["w_out"]


def mamba2_init_cache(cfg: ModelConfig, batch: int) -> Dict[str, jnp.ndarray]:
    di, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * n
    return {
        "state": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), jnp.float32),
    }


def mamba2_decode_step(p: Params, x1: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                       cfg: ModelConfig
                       ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token recurrent step. x1: (B, 1, d)."""
    B = x1.shape[0]
    di, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    proj = x1[:, 0, :] @ p["w_in"]                               # (B, ...)
    z, xBC, dt = jnp.split(proj, [di, 2 * di + 2 * n], axis=-1)
    # conv ring: previous K-1 inputs + current
    hist = cache["conv"]                                         # (B, K-1, ch)
    w = p["conv_w"]
    K = w.shape[0]
    window = jnp.concatenate([hist, xBC[:, None, :].astype(hist.dtype)], axis=1)
    conv = jnp.einsum("bkc,kc->bc", window, w.astype(hist.dtype)) + p["conv_b"]
    xBC_a = jax.nn.silu(conv)
    xs, Bm, Cm = jnp.split(xBC_a, [di, di + n], axis=-1)
    xs = xs.reshape(B, h, cfg.ssm_head_dim).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, h)
    A = -jnp.exp(p["A_log"])
    dec = jnp.exp(dt * A[None, :])                               # (B, h)
    st = cache["state"]                                          # (B,h,p,n)
    xdt = xs * dt[..., None]                                     # (B,h,p)
    st_new = st * dec[..., None, None] + \
        jnp.einsum("bhp,bn->bhpn", xdt, Bm.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", st_new, Cm.astype(jnp.float32))
    y = y + xs * p["D"][None, :, None]
    y = y.reshape(B, di)
    g = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    g = g * lax.rsqrt(var + cfg.norm_eps) * p["norm_scale"]
    out = (g.astype(x1.dtype)) @ p["w_out"]
    new_cache = {"state": st_new,
                 "conv": window[:, 1:, :]}
    return out[:, None, :], new_cache
