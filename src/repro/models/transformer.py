"""Unified decoder stack covering every zoo architecture.

Features, all driven by ``ModelConfig``:

* dense / MoE / SSM (Mamba-2) / hybrid (Jamba-style interleave) mixers,
* GQA attention with RoPE / M-RoPE / none, optional QKV bias, sliding window,
* encoder-decoder (Whisper) with cross-attention,
* stub modality frontends (VLM patch prefix, audio frame encoder input),
* scan-over-layers with per-period parameter stacking so compile time is
  depth-independent (heterogeneous hybrids scan over their repeat period),
* KV / SSM-state caches with single-token ``decode_step`` (ring-buffer cache
  for sliding-window serving).

Everything is pure-functional: ``init_params`` builds the pytree,
``forward`` / ``decode_step`` consume it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .layers import (Params, apply_mrope, apply_rope, attention_forward,
                     decode_attention, embed, init_attention, init_embeddings,
                     init_mlp, init_rmsnorm, mlp_forward, rmsnorm, unembed)
from .mamba2 import (init_mamba2, mamba2_decode_step, mamba2_forward,
                     mamba2_init_cache)
from .moe import init_moe, moe_forward


# --------------------------------------------------------------------- period
def layer_period(cfg: ModelConfig) -> int:
    """Smallest repeating pattern of (mixer kind, moe-ness) over layers."""
    per = 1
    if cfg.arch_type == "hybrid" and cfg.attn_every > 0:
        per = cfg.attn_every
    if cfg.moe_experts > 0 and cfg.moe_every > 1:
        per = _lcm(per, cfg.moe_every)
    if cfg.num_layers % per != 0:
        raise ValueError(f"{cfg.name}: num_layers={cfg.num_layers} not divisible "
                         f"by layer period {per}")
    return per


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------- layer init
def _init_decoder_sublayer(key, cfg: ModelConfig, j: int, dtype) -> Params:
    ks = jax.random.split(key, 6)
    kind = cfg.layer_kind(j)
    p: Params = {"norm1": init_rmsnorm(cfg.d_model)}
    if kind == "attn":
        p["attn"] = init_attention(ks[0], cfg, dtype)
    else:
        p["ssm"] = init_mamba2(ks[0], cfg, dtype)
    if cfg.is_encoder_decoder and kind == "attn":
        p["norm_cross"] = init_rmsnorm(cfg.d_model)
        p["cross"] = init_attention(ks[1], cfg, dtype)
    if cfg.layer_is_moe(j):
        p["norm2"] = init_rmsnorm(cfg.d_model)
        p["moe"] = init_moe(ks[2], cfg, dtype)
    elif cfg.d_ff > 0:
        p["norm2"] = init_rmsnorm(cfg.d_model)
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    return p


def _init_encoder_layer(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "norm1": init_rmsnorm(cfg.d_model),
        "attn": init_attention(ks[0], cfg, dtype),
        "norm2": init_rmsnorm(cfg.d_model),
        "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff or 4 * cfg.d_model,
                        cfg.activation, dtype),
    }


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    per = layer_period(cfg)
    n_per = cfg.num_layers // per
    layers: List[Params] = []
    for j in range(per):
        jkeys = jax.random.split(jax.random.fold_in(keys[0], j), n_per)
        layers.append(jax.vmap(
            lambda k: _init_decoder_sublayer(k, cfg, j, dtype))(jkeys))
    params: Params = {
        "embed": init_embeddings(keys[1], cfg, dtype),
        "layers": layers,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[2], cfg.encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_encoder_layer(k, cfg, dtype))(ekeys)
        params["enc_norm"] = init_rmsnorm(cfg.d_model)
    return params


# ------------------------------------------------------------------- forward
def _default_positions(cfg: ModelConfig, B: int, S: int, offset: int = 0):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope_mode == "mrope":
        return jnp.repeat(pos[..., None], 3, axis=-1)  # text: t==h==w
    return pos


def _decoder_sublayer(p: Params, x, positions, cfg: ModelConfig, j: int,
                      enc_out) -> Tuple[jnp.ndarray, jnp.ndarray]:
    kind = cfg.layer_kind(j)
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        x = x + attention_forward(p["attn"], h, positions, cfg, causal=True)
    else:
        x = x + mamba2_forward(p["ssm"], h, cfg)
    if enc_out is not None and kind == "attn":
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        ck = jnp.einsum("bsd,dhx->bshx", enc_out, p["cross"]["wk"])
        cv = jnp.einsum("bsd,dhx->bshx", enc_out, p["cross"]["wv"])
        x = x + attention_forward(p["cross"], hc, positions, cfg,
                                  causal=False, kv_override=(ck, cv))
    if "moe" in p:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        y, aux = moe_forward(p["moe"], h2, cfg)
        x = x + y
    elif "mlp" in p:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        x = x + mlp_forward(p["mlp"], h2, cfg.activation)
    return x, aux


def _activation_constraint(x: jnp.ndarray) -> jnp.ndarray:
    """Pin (B, S, d) activations to batch-over-data sharding (see
    ParallelContext.constrain_activations)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.context import get_parallel_context
    ctx = get_parallel_context()
    if ctx is None or not ctx.constrain_activations or x.ndim != 3:
        return x
    seq = None
    if ctx.sequence_parallel and x.shape[1] % ctx.tp_size == 0:
        seq = ctx.model_axis
    return lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(ctx.data_spec, seq, None)))


def _run_decoder_stack(params: Params, x, positions, cfg: ModelConfig,
                       enc_out=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    per = layer_period(cfg)

    def period_body(carry, per_params):
        h, aux = carry
        h = _activation_constraint(h)
        for j in range(per):
            h, a = _decoder_sublayer(per_params[j], h, positions, cfg, j,
                                     enc_out)
            aux = aux + a
        return (h, aux), None

    body = period_body
    if cfg.remat:
        body = jax.checkpoint(period_body)
    if cfg.scan_layers:
        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    else:
        aux = jnp.zeros((), jnp.float32)
        n_per = cfg.num_layers // per
        for i in range(n_per):
            sl = jax.tree.map(lambda v: v[i], params["layers"])
            (x, aux), _ = body((x, aux), sl)
    return x, aux


def _sinusoidal(S: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Whisper-style encoder over stub conv-frontend frames (B, T, d)."""
    x = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(frames.dtype)
    ecfg = cfg.with_(rope_mode="none", sliding_window=0)

    def layer(h, p):
        h = _activation_constraint(h)
        a = rmsnorm(p["norm1"], h, cfg.norm_eps)
        h = h + attention_forward(p["attn"], a, None, ecfg, causal=False)
        m = rmsnorm(p["norm2"], h, cfg.norm_eps)
        h = h + mlp_forward(p["mlp"], m, cfg.activation)
        return h, None

    body = jax.checkpoint(layer) if cfg.remat else layer
    x, _ = lax.scan(body, x, params["encoder"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward(params: Params, tokens: jnp.ndarray, cfg: ModelConfig, *,
            positions=None, extra_embeds: Optional[jnp.ndarray] = None,
            frames: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training / prefill forward.

    tokens: (B, S) int32. ``extra_embeds`` (VLM): (B, P, d) patch embeddings
    prepended to the token embeddings. ``frames`` (audio): (B, T, d) stub
    frame embeddings consumed by the encoder.
    Returns (logits (B, S_total, vocab), moe_aux_loss).
    """
    B, S = tokens.shape
    x = embed(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        S = x.shape[1]
    if positions is None:
        positions = _default_positions(cfg, B, S)
    enc_out = None
    if cfg.is_encoder_decoder:
        if frames is None:
            raise ValueError("encoder-decoder model needs `frames`")
        enc_out = encode(params, frames, cfg)
    x, aux = _run_decoder_stack(params, x, positions, cfg, enc_out)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return unembed(params["embed"], x), aux


# --------------------------------------------------------------------- cache
def cache_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window > 0:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    """Decode cache pytree (zeros); shape-compatible with decode_step."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    per = layer_period(cfg)
    n_per = cfg.num_layers // per
    C = cache_len(cfg, max_len)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    layers = []
    for j in range(per):
        kind = cfg.layer_kind(j)
        if kind == "attn":
            entry = {"k": jnp.zeros((n_per, batch, C, kv, hd), dtype),
                     "v": jnp.zeros((n_per, batch, C, kv, hd), dtype)}
        else:
            mc = mamba2_init_cache(cfg, batch)
            entry = {k: jnp.broadcast_to(v, (n_per,) + v.shape).copy()
                     for k, v in mc.items()}
        layers.append(entry)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32), "layers": layers}
    if cfg.is_encoder_decoder:
        cache["cross"] = {
            "k": jnp.zeros((n_per, batch, cfg.encoder_seq, kv, hd), dtype),
            "v": jnp.zeros((n_per, batch, cfg.encoder_seq, kv, hd), dtype),
        }
    return cache


def prepare_cross_cache(params: Params, frames: jnp.ndarray, cfg: ModelConfig
                        ) -> Dict[str, jnp.ndarray]:
    """Whisper: run the encoder once and project per-layer cross K/V."""
    enc = encode(params, frames, cfg)

    per = layer_period(cfg)
    assert per == 1, "enc-dec archs use homogeneous decoder stacks"
    cross = params["layers"][0]["cross"]
    k = jnp.einsum("bsd,ndhx->nbshx", enc, cross["wk"])
    v = jnp.einsum("bsd,ndhx->nbshx", enc, cross["wv"])
    return {"k": k.astype(enc.dtype), "v": v.astype(enc.dtype)}


def _attn_decode_sublayer(p: Params, x1, pos, cache_kv, cfg: ModelConfig,
                          cross_kv=None):
    """x1: (B, 1, d); cache_kv: {'k': (B,C,KV,hd), 'v': ...}."""
    B = x1.shape[0]
    C = cache_kv["k"].shape[1]
    h = rmsnorm(p["norm1"], x1, cfg.norm_eps)
    q = jnp.einsum("bsd,dhx->bshx", h, p["attn"]["wq"])
    k1 = jnp.einsum("bsd,dhx->bshx", h, p["attn"]["wk"])
    v1 = jnp.einsum("bsd,dhx->bshx", h, p["attn"]["wv"])
    if "bq" in p["attn"]:
        q, k1, v1 = q + p["attn"]["bq"], k1 + p["attn"]["bk"], v1 + p["attn"]["bv"]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None], (B, 1))
    if cfg.rope_mode == "standard":
        q = apply_rope(q, posb, cfg.rope_theta)
        k1 = apply_rope(k1, posb, cfg.rope_theta)
    elif cfg.rope_mode == "mrope":
        p3 = jnp.repeat(posb[..., None], 3, axis=-1)
        q = apply_mrope(q, p3, cfg.rope_theta, cfg.mrope_sections)
        k1 = apply_mrope(k1, p3, cfg.rope_theta, cfg.mrope_sections)
    write = pos % C if cfg.sliding_window > 0 else pos
    kc = lax.dynamic_update_slice(cache_kv["k"], k1.astype(cache_kv["k"].dtype),
                                  (0, write, 0, 0))
    vc = lax.dynamic_update_slice(cache_kv["v"], v1.astype(cache_kv["v"].dtype),
                                  (0, write, 0, 0))
    valid = jnp.minimum(pos + 1, C)
    att = decode_attention(q, kc, vc, valid)
    x1 = x1 + jnp.einsum("bshx,hxd->bsd", att, p["attn"]["wo"])
    if cross_kv is not None and "cross" in p:
        hc = rmsnorm(p["norm_cross"], x1, cfg.norm_eps)
        qc = jnp.einsum("bsd,dhx->bshx", hc, p["cross"]["wq"])
        catt = decode_attention(qc, cross_kv["k"], cross_kv["v"],
                                cross_kv["k"].shape[1])
        x1 = x1 + jnp.einsum("bshx,hxd->bsd", catt, p["cross"]["wo"])
    return x1, {"k": kc, "v": vc}


def decode_step(params: Params, cache: Dict[str, Any], tokens1: jnp.ndarray,
                cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step. tokens1: (B, 1) -> logits (B, 1, vocab), new cache."""
    pos = cache["pos"]
    x = embed(params["embed"], tokens1)
    per = layer_period(cfg)

    cross_all = cache.get("cross")

    def period_body(x1, scanned):
        per_params, per_cache, cross_kv = scanned
        ckv = cross_kv if isinstance(cross_kv, dict) else None
        new_cache = []
        for j in range(per):
            p = per_params[j]
            kind = cfg.layer_kind(j)
            if kind == "attn":
                x1, nkv = _attn_decode_sublayer(p, x1, pos, per_cache[j], cfg,
                                                cross_kv=ckv)
                new_cache.append(nkv)
            else:
                h = rmsnorm(p["norm1"], x1, cfg.norm_eps)
                y, nc = mamba2_decode_step(p["ssm"], h, per_cache[j], cfg)
                x1 = x1 + y
                new_cache.append(nc)
            if "moe" in p:
                h2 = rmsnorm(p["norm2"], x1, cfg.norm_eps)
                ym, _ = moe_forward(p["moe"], h2, cfg)
                x1 = x1 + ym
            elif "mlp" in p:
                h2 = rmsnorm(p["norm2"], x1, cfg.norm_eps)
                x1 = x1 + mlp_forward(p["mlp"], h2, cfg.activation)
        return x1, new_cache

    n_per = cfg.num_layers // per
    if cross_all is not None:
        xs = (params["layers"], cache["layers"], cross_all)
    else:
        # scan needs a uniform pytree; dummy empty leaf stands in for cross
        xs = (params["layers"], cache["layers"], jnp.zeros((n_per, 0)))
    x, new_layers = lax.scan(period_body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = pos + 1
    return logits, new_cache
