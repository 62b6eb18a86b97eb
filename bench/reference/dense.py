"""Plain dense decoder (GQA attention with rotary positions, SwiGLU MLP,
RMSNorm before each block), in float32.

Layout of the weights (``L`` layers stacked on the first axis):
``embed.tok`` (vocab, d) and the untied head ``embed.unembed`` (d, vocab);
per layer ``norm1.scale``, ``attn`` with ``wq`` (d, H, hd), ``wk`` and
``wv`` (d, KV, hd), ``wo`` (H, hd, d) and, with ``qkv_bias``, ``bq``,
``bk``, ``bv``; ``norm2.scale`` and ``mlp`` with ``w_gate``, ``w_up``
(d, d_ff) and ``w_down`` (d_ff, d); then ``final_norm.scale``.

Rotary positions turn each head's first half against its second half at
frequencies theta ** (-i / (hd / 2)). Query head j reads key and value head
j // (H / KV).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .numerics import cross_entropy, normal, rmsnorm


TINY = dict(name="dense-tiny", arch_type="dense", num_layers=2,
            d_model=128, vocab_size=512, num_heads=4, num_kv_heads=2,
            head_dim=32, d_ff=256, activation="swiglu", qkv_bias=True,
            rope_theta=10000.0, rope_mode="standard",
            norm_eps=1.5625e-07, tie_embeddings=False,
            dtype="bfloat16", remat=True, scan_layers=True)


def _dims(m: Dict):
    if m.get("activation", "swiglu") != "swiglu":
        raise ValueError(f"dense reference is SwiGLU only, not "
                         f"{m['activation']!r}")
    d, H, KV = m["d_model"], m["num_heads"], m["num_kv_heads"]
    return d, H, KV, m.get("head_dim") or d // H, m["d_ff"]


def macs_per_token(m: Dict, seq: int) -> float:
    """Forward multiply-adds per token (``bench.flops``' rules). Per layer:
    the q, k, v and output projections, then (S + 1) / 2 keys on average
    for q.k and again for p.v, for each of the heads; the SwiGLU MLP's
    three d x d_ff matrices. Then the output head, d x vocab."""
    d, h, kv, hd, f = _dims(m)
    proj = d * (h + 2 * kv) * hd + h * hd * d
    mix = 2 * h * hd * (seq + 1) / 2
    return m["num_layers"] * (proj + mix + 3 * d * f) + d * m["vocab_size"]


def served_dtypes(m: Dict) -> Dict:
    w, f = m.get("dtype", "bfloat16"), "float32"
    attn = {"wq": w, "wk": w, "wv": w, "wo": w}
    if m.get("qkv_bias"):
        attn.update(bq=w, bk=w, bv=w)
    return {"embed": {"tok": w, "unembed": w},
            "layers": [{"norm1": {"scale": f}, "attn": attn,
                        "norm2": {"scale": f},
                        "mlp": {"w_up": w, "w_down": w, "w_gate": w}}],
            "final_norm": {"scale": f}}


def init(m: Dict, key) -> Dict:
    d, H, KV, hd, f = _dims(m)
    L, V = m["num_layers"], m["vocab_size"]
    ks = jax.random.split(key, 12)
    attn = {"wq": normal(ks[2], (L, d, H, hd), d ** -0.5),
            "wk": normal(ks[3], (L, d, KV, hd), d ** -0.5),
            "wv": normal(ks[4], (L, d, KV, hd), d ** -0.5),
            "wo": normal(ks[5], (L, H, hd, d), (H * hd) ** -0.5)}
    if m.get("qkv_bias"):
        attn.update(bq=normal(ks[9], (L, H, hd), 0.02),
                    bk=normal(ks[10], (L, KV, hd), 0.02),
                    bv=normal(ks[11], (L, KV, hd), 0.02))
    p = {"embed": {"tok": normal(ks[0], (V, d), d ** -0.5),
                   "unembed": normal(ks[1], (d, V), d ** -0.5)},
         "layers": [{"norm1": {"scale": jnp.ones((L, d))}, "attn": attn,
                     "norm2": {"scale": jnp.ones((L, d))},
                     "mlp": {"w_up": normal(ks[6], (L, d, f), d ** -0.5),
                             "w_down": normal(ks[7], (L, f, d), f ** -0.5),
                             "w_gate": normal(ks[8], (L, d, f), d ** -0.5)}}],
         "final_norm": {"scale": jnp.ones((d,))}}
    return p


def _rotate(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x (b, s, heads, hd) at positions 0 .. s-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _attention(w, x, m: Dict, mm):
    d, H, KV, hd, _ = _dims(m)
    q = mm("bsd,dhk->bshk", x, w["wq"])
    k = mm("bsd,dhk->bshk", x, w["wk"])
    v = mm("bsd,dhk->bshk", x, w["wv"])
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if m.get("rope_mode", "standard") == "standard":
        q, k = _rotate(q, m["rope_theta"]), _rotate(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    S = x.shape[1]
    s = mm("bqhk,bshk->bhqs", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return mm("bqhk,hkd->bqd", o, w["wo"])


def _mlp(w, x, mm):
    gate = jax.nn.silu(mm("bsd,df->bsf", x, w["w_gate"]))
    return mm("bsf,fd->bsd", gate * mm("bsd,df->bsf", x, w["w_up"]),
              w["w_down"])


def loss(params, tokens, labels, m: Dict, mm) -> jnp.ndarray:
    eps = m["norm_eps"]
    x = params["embed"]["tok"][tokens]

    @jax.checkpoint
    def layer(x, w):
        x = x + _attention(w["attn"], rmsnorm(x, w["norm1"]["scale"], eps),
                           m, mm)
        return x + _mlp(w["mlp"], rmsnorm(x, w["norm2"]["scale"], eps),
                        mm), None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = rmsnorm(x, params["final_norm"]["scale"], eps)
    return cross_entropy(mm("bsd,dv->bsv", x, params["embed"]["unembed"]),
                         labels)
