"""Mixture-of-Experts layer: shared + routed experts, top-k routing with
fixed capacity, sort+scatter dispatch (no O(tokens^2) one-hot einsums).

Two execution paths:

* ``dense``  — single-program dispatch with GSPMD sharding constraints
               (experts sharded over the ``model`` mesh axis). Default; also
               the single-device smoke-test path.
* ``ep``     — explicit expert parallelism under ``shard_map``: every model
               shard routes its (replicated) token set to its *local* experts
               and the partial outputs are combined with one ``psum`` over the
               model axis. This is the paper-faithful "switch aggregation"
               analogue (partial sums combined in the fabric) and the baseline
               that the §Perf all-to-all iteration improves on.

Routing follows DeepSeekMoE / Qwen2-MoE: softmax -> top-k -> renormalize,
plus a Switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import init_mlp, mlp_forward
from repro.parallel.context import get_parallel_context

Params = Dict[str, jnp.ndarray]


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, e, f = cfg.d_model, cfg.moe_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": (jax.random.normal(ks[0], (d, e)) * d ** -0.5).astype(jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, d, f)) * d ** -0.5).astype(dtype),
        "w_gate": (jax.random.normal(ks[2], (e, d, f)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * f ** -0.5).astype(dtype),
    }
    if cfg.d_ff > 0:  # shared expert(s), fused into one MLP of width d_ff
        p["shared"] = init_mlp(ks[4], d, cfg.d_ff, "swiglu", dtype)
    return p


def _route(p: Params, x2d: jnp.ndarray, cfg: ModelConfig
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Return (weights (N,k), expert ids (N,k), aux loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ p["router"])            # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = lax.top_k(probs, cfg.moe_top_k)              # (N, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e fraction_e * prob_e
    e = cfg.moe_experts
    frac = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    frac = frac / top_e.size
    pmean = probs.mean(axis=0)
    aux = e * jnp.sum(frac * pmean)
    return top_w, top_e, aux


def _dispatch_indices(top_e: jnp.ndarray, k: int, num_experts: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort slots by expert; return (sorted expert id, position-in-expert,
    source slot order). Cheap O(Nk log Nk) — no one-hot matmuls."""
    flat_e = top_e.reshape(-1)                                   # (N*k,)
    order = jnp.argsort(flat_e)                                  # stable
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(sorted_e.shape[0]) - first
    return sorted_e, pos_in_e, order


def _expert_ffn(p: Params, buf: jnp.ndarray) -> jnp.ndarray:
    """buf: (E, C, d) -> (E, C, d) through each expert's SwiGLU FFN."""
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.moe_top_k * cfg.moe_capacity_factor
            / cfg.moe_experts) + 1
    return max(8, -(-c // 8) * 8)  # pad to a multiple of 8 for TPU layouts


def _moe_dense(p: Params, x2d: jnp.ndarray, cfg: ModelConfig,
               model_axis: Optional[str]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n, d = x2d.shape
    k = cfg.moe_top_k
    top_w, top_e, aux = _route(p, x2d, cfg)
    sorted_e, pos_in_e, order = _dispatch_indices(top_e, k, cfg.moe_experts)
    cap = _capacity(n, cfg)
    keep = pos_in_e < cap
    src_tok = order // k
    buf = jnp.zeros((cfg.moe_experts, cap, d), dtype=x2d.dtype)
    buf = buf.at[sorted_e, jnp.where(keep, pos_in_e, cap)].set(
        x2d[src_tok], mode="drop")

    def _constrain(t):
        ctx = get_parallel_context()
        if model_axis is None or ctx is None:
            return t
        tp = ctx.mesh.shape[model_axis]
        # shard experts over the model axis when divisible, else the
        # capacity dim (qwen2-moe's 60 experts on a 16-way axis)
        from jax.sharding import NamedSharding
        if t.shape[0] % tp == 0:
            spec = P(model_axis, None, None)
        elif t.shape[1] % tp == 0:
            spec = P(None, model_axis, None)
        else:
            return t
        return lax.with_sharding_constraint(t, NamedSharding(ctx.mesh, spec))

    buf = _constrain(buf)
    out = _expert_ffn(p, buf)
    out = _constrain(out)
    vals = out[sorted_e, jnp.minimum(pos_in_e, cap - 1)]
    vals = jnp.where(keep[:, None], vals, 0.0)
    w_sorted = top_w.reshape(-1)[order].astype(vals.dtype)
    y = jnp.zeros((n, d), dtype=x2d.dtype)
    y = y.at[src_tok].add(vals * w_sorted[:, None])
    return y, aux


def _moe_ep_shardmap(p: Params, x: jnp.ndarray, cfg: ModelConfig
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel path: local-expert dispatch + psum combine.

    Tokens are replicated along the model axis; each shard serves only its
    E/tp local experts and contributes a partial output, summed with one
    ``psum`` — the direct analogue of Canary's in-fabric partial aggregation.
    """
    ctx = get_parallel_context()
    mesh, maxis = ctx.mesh, ctx.model_axis
    tp = mesh.shape[maxis]
    e_loc = cfg.moe_experts // tp
    B, S, d = x.shape
    # decode batches can be smaller than the data-parallel degree (e.g.
    # long_500k has batch 1): replicate tokens over the data axes then
    dp_spec = ctx.data_spec if B % ctx.dp_size == 0 else None

    def local(px, xx):
        n = xx.shape[0] * xx.shape[1]
        x2d = xx.reshape(n, d)
        k = cfg.moe_top_k
        top_w, top_e, aux = _route(px, x2d, cfg)
        shard = lax.axis_index(maxis)
        lo = shard * e_loc
        sorted_e, pos_in_e, order = _dispatch_indices(top_e, k, cfg.moe_experts)
        cap = _capacity(n, cfg)
        local_ok = (sorted_e >= lo) & (sorted_e < lo + e_loc) & (pos_in_e < cap)
        src_tok = order // k
        buf = jnp.zeros((e_loc, cap, d), dtype=x2d.dtype)
        buf = buf.at[jnp.where(local_ok, sorted_e - lo, e_loc),
                     jnp.where(local_ok, pos_in_e, cap)].set(
            x2d[src_tok], mode="drop")
        # local experts only: slice the (already sharded) weights arrive whole
        out = _expert_ffn(px, buf)
        vals = out[jnp.clip(sorted_e - lo, 0, e_loc - 1),
                   jnp.minimum(pos_in_e, cap - 1)]
        vals = jnp.where(local_ok[:, None], vals, 0.0)
        w_sorted = top_w.reshape(-1)[order].astype(vals.dtype)
        y = jnp.zeros((n, d), dtype=x2d.dtype)
        y = y.at[src_tok].add(vals * w_sorted[:, None])
        y = lax.psum(y, maxis)                      # combine expert partials
        aux = lax.pmean(aux, maxis)
        return y.reshape(xx.shape), aux

    pspec_params = {
        "router": P(),
        "w_up": P(maxis, None, None),
        "w_gate": P(maxis, None, None),
        "w_down": P(maxis, None, None),
    }
    in_specs = ({k: pspec_params.get(k, P()) for k in p if k != "shared"},
                P(dp_spec, None, None))
    out_specs = (P(dp_spec, None, None), P())
    routed = {k: v for k, v in p.items() if k != "shared"}
    y, aux = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)(routed, x)
    return y, aux


def _dp_size(mesh, dp_spec) -> int:
    if isinstance(dp_spec, str):
        return mesh.shape[dp_spec]
    return int(jnp.prod(jnp.array([mesh.shape[a] for a in dp_spec])))


def _moe_ep_a2a_shardmap(p: Params, x: jnp.ndarray, cfg: ModelConfig
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All-to-all expert parallelism (beyond the psum baseline, §Perf-2).

    Tokens are *sequence-sharded* over the model axis; each shard routes its
    own tokens, packs them per destination shard, and two ``all_to_all``s
    carry them to the expert owners and back. Per-device link bytes are
    ~2·k/tp of the token stream vs ~2x for the psum combine — the classic
    DeepSpeed-MoE/Switch schedule, and the same "send only what must move"
    idea Canary applies to reduction traffic.
    """
    ctx = get_parallel_context()
    mesh, maxis = ctx.mesh, ctx.model_axis
    tp = mesh.shape[maxis]
    e_loc = cfg.moe_experts // tp
    B, S, d = x.shape
    dp_spec = ctx.data_spec if B % ctx.dp_size == 0 else None

    def local(px, xx):
        b_loc, s_loc, _ = xx.shape
        n = b_loc * s_loc
        x2d = xx.reshape(n, d)
        k = cfg.moe_top_k
        top_w, top_e, aux = _route(px, x2d, cfg)
        flat_e = top_e.reshape(-1)                       # (n*k,)
        flat_w = top_w.reshape(-1)
        dest = flat_e // e_loc                           # destination shard
        order = jnp.argsort(dest)
        sd = dest[order]
        first = jnp.searchsorted(sd, sd, side="left")
        pos = jnp.arange(sd.shape[0]) - first            # rank within dest
        cap = max(8, -(-int(n * k / tp * cfg.moe_capacity_factor) // 8) * 8)
        ok = pos < cap
        src_slot = order                                  # (n*k,) originating slot
        send_x = jnp.zeros((tp, cap, d), x2d.dtype).at[
            jnp.where(ok, sd, tp), jnp.where(ok, pos, cap)].set(
            x2d[src_slot // k], mode="drop")
        send_e = jnp.full((tp, cap), cfg.moe_experts, jnp.int32).at[
            jnp.where(ok, sd, tp), jnp.where(ok, pos, cap)].set(
            flat_e[order], mode="drop")
        # ship to expert owners
        recv_x = lax.all_to_all(send_x, maxis, split_axis=0, concat_axis=0,
                                tiled=True)              # (tp*cap, d)? tiled
        recv_e = lax.all_to_all(send_e, maxis, split_axis=0, concat_axis=0,
                                tiled=True)
        recv_x = recv_x.reshape(tp * cap, d)
        recv_e = recv_e.reshape(tp * cap)
        shard = lax.axis_index(maxis)
        le = recv_e - shard * e_loc                      # local expert id
        valid = (le >= 0) & (le < e_loc)
        order2 = jnp.argsort(jnp.where(valid, le, e_loc))
        se2 = jnp.where(valid, le, e_loc)[order2]
        first2 = jnp.searchsorted(se2, se2, side="left")
        pos2 = jnp.arange(se2.shape[0]) - first2
        cap2 = max(8, -(-int(tp * cap / e_loc
                             * cfg.moe_capacity_factor) // 8) * 8)
        ok2 = (pos2 < cap2) & (se2 < e_loc)
        buf = jnp.zeros((e_loc, cap2, d), x2d.dtype).at[
            jnp.where(ok2, se2, e_loc), jnp.where(ok2, pos2, cap2)].set(
            recv_x[order2], mode="drop")
        out = _expert_ffn(px, buf)
        # inverse local permutation back to (tp*cap, d)
        vals2 = out[jnp.clip(se2, 0, e_loc - 1), jnp.minimum(pos2, cap2 - 1)]
        vals2 = jnp.where(ok2[:, None], vals2, 0.0)
        back_flat = jnp.zeros((tp * cap, d), x2d.dtype).at[order2].set(vals2)
        back = lax.all_to_all(back_flat.reshape(tp, cap, d), maxis,
                              split_axis=0, concat_axis=0, tiled=True)
        back = back.reshape(tp, cap, d)
        # combine at source: slot (dest, pos) -> original token
        got = back[jnp.minimum(sd, tp - 1), jnp.minimum(pos, cap - 1)]
        got = jnp.where(ok[:, None], got, 0.0)
        w_sorted = flat_w[order].astype(got.dtype)
        y = jnp.zeros((n, d), x2d.dtype).at[src_slot // k].add(
            got * w_sorted[:, None])
        aux = lax.pmean(aux, maxis)
        return y.reshape(xx.shape), aux

    pspec_params = {
        "router": P(),
        "w_up": P(maxis, None, None),
        "w_gate": P(maxis, None, None),
        "w_down": P(maxis, None, None),
    }
    routed = {kk: v for kk, v in p.items() if kk != "shared"}
    in_specs = ({kk: pspec_params.get(kk, P()) for kk in routed},
                P(dp_spec, maxis, None))
    out_specs = (P(dp_spec, maxis, None), P())
    y, aux = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)(routed, x)
    return y, aux


def moe_forward(p: Params, x: jnp.ndarray, cfg: ModelConfig
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss). Shared experts always run densely."""
    B, S, d = x.shape
    ctx = get_parallel_context()
    impl = cfg.moe_impl
    if impl == "auto":
        use_ep = (ctx is not None and ctx.allow_shardmap_layers
                  and ctx.mesh.shape[ctx.model_axis] > 1
                  and cfg.moe_experts % ctx.mesh.shape[ctx.model_axis] == 0)
        impl = "ep" if use_ep else "dense"
    if impl == "ep_a2a" and ctx is not None and ctx.allow_shardmap_layers:
        tp = ctx.mesh.shape[ctx.model_axis]
        if S % tp == 0 and cfg.moe_experts % tp == 0:
            y, aux = _moe_ep_a2a_shardmap(p, x, cfg)
        else:  # decode (S=1) or non-divisible: fall back to psum combine
            y, aux = _moe_ep_shardmap(p, x, cfg)
    elif impl == "ep" and ctx is not None and ctx.allow_shardmap_layers:
        y, aux = _moe_ep_shardmap(p, x, cfg)
    else:
        maxis = ctx.model_axis if ctx is not None else None
        y2d, aux = _moe_dense(p, x.reshape(B * S, d), cfg, maxis)
        y = y2d.reshape(B, S, d)
    if "shared" in p:
        y = y + mlp_forward(p["shared"], x, "swiglu")
    return y, aux
