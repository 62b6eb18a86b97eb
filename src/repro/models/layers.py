"""Core neural layers: RMSNorm, RoPE / M-RoPE, GQA attention (full, chunked
online-softmax, sliding-window decode), and dense MLPs.

All functions are pure; parameters are plain pytrees created by the ``init_*``
helpers. Shapes follow the (batch, seq, heads, head_dim) convention.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Params = Dict[str, jnp.ndarray]


# --------------------------------------------------------------------- norms
def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Standard rotary embedding. x: (B, S, H, D); positions: (B, S)."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191 §3.1).

    The head_dim/2 frequency slots are split into (t, h, w) sections; each
    section rotates by its own position stream. ``positions3``: (B, S, 3).
    For pure text all three streams are equal and M-RoPE == RoPE.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                       # (half,)
    sec_ids = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)
    ])                                                           # (half,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                          # (B, S, 3)
        jnp.broadcast_to(sec_ids[None, None, :], positions3.shape[:2] + (half,)),
        axis=-1)                                                 # (B, S, half)
    ang = pos * freqs                                            # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ attention
def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, h, hd)) * scale).astype(dtype),
        "wk": (jax.random.normal(k2, (d, kv, hd)) * scale).astype(dtype),
        "wv": (jax.random.normal(k3, (d, kv, hd)) * scale).astype(dtype),
        "wo": (jax.random.normal(k4, (h, hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype=dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype=dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype=dtype)
    return p


def _gqa_logits(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """q: (B, Sq, H, D), k: (B, Sk, KV, D) -> logits (B, KV, G, Sq, Sk)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / jnp.sqrt(D).astype(q.dtype)


def _gqa_out(probs: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """probs: (B, KV, G, Sq, Sk), v: (B, Sk, KV, D) -> (B, Sq, H, D)."""
    B, KV, G, Sq, _ = probs.shape
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, KV * G, -1)


def full_attention(q, k, v, *, causal: bool, sliding_window: int = 0,
                   q_offset: int = 0) -> jnp.ndarray:
    """Materialized-logits attention (short sequences)."""
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    logits = _gqa_logits(q, k).astype(jnp.float32)
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if sliding_window > 0:
        mask &= kpos > qpos - sliding_window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                      sliding_window: int = 0) -> jnp.ndarray:
    """Online-softmax attention, O(chunk^2) memory (FlashAttention recurrence).

    Scans over query blocks (outer) and key/value blocks (inner), carrying the
    (max, sum, acc) online-softmax state. Block-level causal masking is
    applied inside the scan; fully-masked blocks still issue their matmuls
    (a known ~2x score-FLOP overhead vs. a triangular kernel — the Pallas
    flash kernel in ``repro.kernels.flash_attention`` skips them on TPU; see
    EXPERIMENTS.md §Roofline for the accounting).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    G = H // KV
    qb = q.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)
    kb = k.reshape(B, n, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n, chunk, KV, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk

        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, kblk, vblk = kj_blk
            logits = _gqa_logits(qblk, kblk).astype(jnp.float32)
            qpos = qi * chunk + jnp.arange(chunk)[:, None]
            kpos = kj * chunk + jnp.arange(chunk)[None, :]
            mask = jnp.ones((chunk, chunk), dtype=bool)
            if causal:
                mask &= kpos <= qpos
            if sliding_window > 0:
                mask &= kpos > qpos - sliding_window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(qblk.dtype), vblk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, chunk), -1e30, dtype=jnp.float32)
        l0 = jnp.zeros((B, KV, G, chunk), dtype=jnp.float32)
        a0 = jnp.zeros((B, KV, G, chunk, D), dtype=qblk.dtype)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (jnp.arange(n), kb, vb))
        out = acc / jnp.maximum(l, 1e-20)[..., None].astype(acc.dtype)
        # (B, KV, G, chunk, D) -> (B, chunk, H, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, chunk, H, D)
        return None, out

    _, outs = lax.scan(q_step, None, (jnp.arange(n), qb))
    # (n, B, chunk, H, D) -> (B, S, H, D)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def decode_attention(q1, k_cache, v_cache, valid_len, *,
                     ring: bool = False, window: int = 0,
                     write_pos: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Single-query attention against a KV cache.

    q1: (B, 1, H, D); caches: (B, C, KV, D). ``valid_len`` (scalar or (B,))
    marks how many slots are populated. For sliding-window serving the cache
    is a ring buffer of size ``window`` — every populated slot is in-window
    by construction, so only validity masking is required.
    """
    B, C = k_cache.shape[0], k_cache.shape[1]
    logits = _gqa_logits(q1, k_cache).astype(jnp.float32)  # (B,KV,G,1,C)
    slot = jnp.arange(C)[None, :]                          # (1, C)
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = jnp.broadcast_to(vl, (B,))
    mask = slot < vl[:, None]                              # (B, C)
    logits = jnp.where(mask[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q1.dtype)
    return _gqa_out(probs, v_cache)


def attention_forward(p: Params, x: jnp.ndarray, positions, cfg: ModelConfig,
                      *, causal: bool = True,
                      kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                      ) -> jnp.ndarray:
    """Projection + RoPE + attention for training / prefill.

    ``kv_override`` supplies externally-computed K/V (cross-attention)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhx->bshx", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if kv_override is None:
        k = jnp.einsum("bsd,dhx->bshx", x, p["wk"])
        v = jnp.einsum("bsd,dhx->bshx", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        if cfg.rope_mode == "standard":
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        elif cfg.rope_mode == "mrope":
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        k, v = kv_override
        # cross-attention: rotary on neither side (whisper convention)

    if S >= cfg.attn_chunk_threshold and S % cfg.attn_chunk == 0 \
            and kv_override is None:
        out = chunked_attention(q, k, v, causal=causal, chunk=cfg.attn_chunk,
                                sliding_window=cfg.sliding_window)
    else:
        out = full_attention(q, k, v, causal=causal,
                             sliding_window=cfg.sliding_window)
    return jnp.einsum("bshx,hxd->bsd", out, p["wo"])


# ----------------------------------------------------------------------- MLP
def init_mlp(key, d: int, f: int, activation: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": (jax.random.normal(ks[0], (d, f)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[1], (f, d)) * f ** -0.5).astype(dtype),
    }
    if activation == "swiglu":
        p["w_gate"] = (jax.random.normal(ks[2], (d, f)) * d ** -0.5).astype(dtype)
    return p


def mlp_forward(p: Params, x: jnp.ndarray, activation: str) -> jnp.ndarray:
    up = x @ p["w_up"]
    if activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * up
    elif activation == "squared_relu":          # Nemotron-4 (arXiv:2402.16819)
        h = jnp.square(jax.nn.relu(up))
    elif activation == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(f"unknown activation {activation}")
    return h @ p["w_down"]


# ----------------------------------------------------------------- embeddings
def init_embeddings(key, cfg: ModelConfig, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))
                 * cfg.d_model ** -0.5).astype(dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                        * cfg.d_model ** -0.5).astype(dtype)
    return p


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "unembed" in p:
        return x @ p["unembed"]
    return x @ p["tok"].T
