"""Plain Mamba-2 language model (arXiv:2405.21060), in float32.

Layout of the weights (``L`` layers stacked on the first axis):
``embed.tok`` (vocab, d), tied to the output head; per layer ``norm1.scale``
and the mixer ``ssm``: ``w_in`` (d, 2 di + 2 n + h) giving z, x B C and dt,
the causal depthwise convolution ``conv_w`` (K, di + 2 n) and ``conv_b``,
``A_log``, ``D``, ``dt_bias`` (h,), the gated norm's ``norm_scale`` (di,)
and ``w_out`` (di, d); then ``final_norm.scale``.

The scan is the paper's minimal SSD listing ("ssd_minimal_discrete"), with
its stable segment sums, at a block length of its own. One group (B and C
shared by the heads).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .numerics import cross_entropy, normal, rmsnorm, uniform

BLOCK = 64   # SSD block length of the reference

TINY = dict(name="mamba2-tiny", arch_type="ssm", num_layers=2,
            d_model=128, vocab_size=512, d_ff=0, rope_mode="none",
            ssm_state=16, ssm_expand=2, ssm_head_dim=32, ssm_chunk=16,
            tie_embeddings=True, norm_eps=1e-5, dtype="bfloat16",
            remat=True, scan_layers=True)


def _dims(m: Dict):
    d = m["d_model"]
    di = m.get("ssm_expand", 2) * d
    n, p = m["ssm_state"], m.get("ssm_head_dim", 64)
    return d, di, n, di // p, p, m.get("ssm_conv", 4)


def macs_per_token(m: Dict, seq: int) -> float:
    """Forward multiply-adds per token (``bench.flops``' rules). Per layer:
    the input projection d -> (2 di + 2 n + h), the depthwise causal
    convolution (K taps on di + 2 n channels), the output projection
    di -> d, and the SSD scan with chunk length Q: (Q + 1) / 2 positions
    for C.B and for applying it to the h x p inputs within a chunk, h p n
    to write each token into the chunk state, and h p n to read the state
    back out. Then the tied output head, d x vocab. Independent of the
    sequence length."""
    d, di, n, h, p, k = _dims(m)
    q = m.get("ssm_chunk", 128)
    proj = d * (2 * di + 2 * n + h) + di * d
    conv = k * (di + 2 * n)
    ssd = (q + 1) / 2 * n + (q + 1) / 2 * h * p + 2 * h * p * n
    return m["num_layers"] * (proj + conv + ssd) + d * m["vocab_size"]


def served_dtypes(m: Dict) -> Dict:
    w = m.get("dtype", "bfloat16")
    f = "float32"
    return {"embed": {"tok": w},
            "layers": [{"norm1": {"scale": f},
                        "ssm": {"w_in": w, "conv_w": w, "conv_b": w,
                                "A_log": f, "D": f, "dt_bias": f,
                                "norm_scale": f, "w_out": w}}],
            "final_norm": {"scale": f}}


def init(m: Dict, key) -> Dict:
    """The published initialisation (``mamba_ssm``'s ``Mamba2`` and
    ``MixerModel``): linear and convolution weights uniform within
    1 / sqrt(fan in), the output projection then divided by sqrt(layers),
    the tied embedding normal with std 0.02, the step size dt log-uniform
    in [1e-3, 1e-1] through ``dt_bias`` (the inverse softplus of dt), A
    uniform in [1, 16]. Without the division the 24-layer stack amplifies
    a relative perturbation of its input some fifty times in the first
    gradient's norms."""
    d, di, n, h, _, k = _dims(m)
    L, V = m["num_layers"], m["vocab_size"]
    ks = jax.random.split(key, 7)
    ch = di + 2 * n
    dt = jnp.exp(jax.random.uniform(ks[4], (L, h), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {"embed": {"tok": normal(ks[0], (V, d), 0.02)},
            "layers": [{"norm1": {"scale": jnp.ones((L, d))},
                        "ssm": {
                            "w_in": uniform(ks[1], (L, d, 2 * di + 2 * n + h),
                                            d ** -0.5),
                            "conv_w": uniform(ks[2], (L, k, ch), k ** -0.5),
                            "conv_b": uniform(ks[3], (L, ch), k ** -0.5),
                            "A_log": jnp.log(jax.random.uniform(
                                ks[5], (L, h), minval=1.0, maxval=16.0)),
                            "D": jnp.ones((L, h)),
                            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                            "norm_scale": jnp.ones((L, di)),
                            "w_out": uniform(ks[6], (L, di, d), di ** -0.5)
                            / L ** 0.5}}],
            "final_norm": {"scale": jnp.ones((d,))}}


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., T) -> (..., T, T): out[i, j] = x[j+1] + ... + x[i] for j <= i,
    -inf above the diagonal; summed without subtracting two cumsums."""
    T = x.shape[-1]
    rep = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    rep = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), rep, 0.0)
    out = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), out, -jnp.inf)


def ssd(X, A, B, C, block: int, mm):
    """y[t] = sum over s <= t of C[t].B[s] exp(A[s+1] + ... + A[t]) X[s].

    X (b, l, h, p), A (b, l, h), B and C (b, l, n)."""
    b, l, h, p = X.shape
    c = l // block
    X = X.reshape(b, c, block, h, p)
    B = B.reshape(b, c, block, -1)
    C = C.reshape(b, c, block, -1)
    A = A.reshape(b, c, block, h).transpose(0, 3, 1, 2)       # b h c l
    A_cs = jnp.cumsum(A, axis=-1)
    # within a block
    L = jnp.exp(_segsum(A))
    y_diag = mm("bcln,bcsn,bhcls,bcshp->bclhp", C, B, L, X)
    # state of each block's end, then passed from block to block
    decay = jnp.exp(A_cs[..., -1:] - A_cs)
    states = mm("bcln,bhcl,bclhp->bchpn", B, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = mm("bhzc,bchpn->bzhpn", chunk, states)[:, :-1]
    # state to output
    y_off = mm("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cs))
    return (y_diag + y_off).reshape(b, l, h, p)


def _mixer(w, x, m: Dict, mm):
    d, di, n, h, p, k = _dims(m)
    b, S, _ = x.shape
    proj = mm("bsd,de->bse", x, w["w_in"])
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    # causal depthwise convolution: out[t] = sum_j w[j] in[t - (K-1) + j]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, j:j + S] * w["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[..., :di].reshape(b, S, h, p)
    Bm, Cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    y = ssd(xs * dt[..., None], dt * A, Bm, Cm, BLOCK, mm)
    y = (y + xs * w["D"][:, None]).reshape(b, S, di)
    g = rmsnorm(y * jax.nn.silu(z), w["norm_scale"], m["norm_eps"])
    return mm("bse,ed->bsd", g, w["w_out"])


def loss(params, tokens, labels, m: Dict, mm) -> jnp.ndarray:
    x = params["embed"]["tok"][tokens]

    @jax.checkpoint
    def layer(x, w):
        return x + _mixer(w["ssm"], rmsnorm(x, w["norm1"]["scale"],
                                            m["norm_eps"]), m, mm), None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    return cross_entropy(mm("bsd,vd->bsv", x, params["embed"]["tok"]), labels)
