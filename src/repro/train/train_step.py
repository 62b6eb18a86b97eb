"""Training step factory with pluggable gradient synchronization.

``grad_sync`` strategies:

* ``auto``          — GSPMD inserts the gradient collectives implied by the
                      param shardings (FSDP: reduce-scatter; replicated:
                      all-reduce). The performance baseline.
* ``canary``        — the paper's technique: per-data-shard gradients are
                      reduced explicitly with blockwise multi-root dynamic
                      trees (``canary_allreduce_tree``) inside a
                      partial-auto ``shard_map`` (manual over the data axes,
                      the model axis stays GSPMD-automatic).
* ``ring``          — explicit bandwidth-optimal reduce-scatter/all-gather
                      (the paper's host-based baseline).
* ``hierarchical``  — pod-local reduce-scatter, cross-pod exchange,
                      pod-local all-gather (the in-switch aggregation
                      analogue; multi-pod meshes only).
* ``canary_fp``     — canary + fixed-point (int32) blocks: bit-reproducible
                      sums regardless of tree shape (paper §6 + beyond-paper
                      determinism).

Explicit grad-sync modes require params *replicated* over the data axes
(``use_fsdp=False``) since they perform the data-axis reduction themselves.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.collective import canary_allreduce_tree
from repro.models import forward
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, AdamWState
from repro.optim import init as adamw_init
from repro.optim import update as adamw_update
from repro.parallel.sharding import param_specs
from .losses import cross_entropy

EXPLICIT_MODES = ("canary", "ring", "hierarchical", "canary_fp")


@dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    optimizer: AdamWConfig = AdamWConfig()
    grad_sync: str = "auto"
    canary_blocks: int = 16
    canary_roots: Optional[Tuple[int, ...]] = None  # congestion-oracle plan
    z_loss: float = 0.0
    # gradient accumulation: split the global batch into k microbatches and
    # scan over them — activation memory scales with B/k (§Perf lever)
    microbatches: int = 1


def make_loss_fn(tc: TrainConfig, constrain: str = "full") -> Callable:
    """``constrain``: 'full' (batch->data, vocab->model), 'model' (vocab only
    — safe inside a data-manual shard_map), or 'none'."""
    cfg = tc.model

    # the backward pass carries this scope as transpose(jvp(forward)), and
    # remat's recompute as rematted_computation under it
    @jax.named_scope("forward")
    def loss_fn(params, batch):
        from jax.sharding import NamedSharding
        from repro.parallel.context import get_parallel_context
        ctx = get_parallel_context()
        kwargs = {}
        if "frames" in batch:
            kwargs["frames"] = batch["frames"]
        if "patches" in batch:
            kwargs["extra_embeds"] = batch["patches"]
        logits, aux = forward(params, batch["tokens"], cfg, **kwargs)
        if ctx is not None and constrain != "none":
            # keep the (B, S, V) logits sharded: batch over the data axes,
            # vocab over the model axis — without this constraint GSPMD may
            # materialize replicated logits (tens of GiB at 4k x 256)
            spec = P(ctx.data_spec, None, ctx.model_axis) \
                if constrain == "full" else P(None, None, ctx.model_axis)
            logits = jax.lax.with_sharding_constraint(
                logits, NamedSharding(ctx.mesh, spec))
        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:   # VLM prefix: score text only
            logits = logits[:, logits.shape[1] - labels.shape[1]:]
        loss, metrics = cross_entropy(logits, labels, z_loss=tc.z_loss)
        total = loss + cfg.moe_aux_coef * aux
        metrics["aux_loss"] = aux
        return total, metrics

    return loss_fn


def make_grads_fn(tc: TrainConfig, mesh: Optional[Mesh] = None,
                  dp_axes: Tuple[str, ...] = ("data",)) -> Callable:
    """Returns grads_fn(params, batch) -> (grads, metrics): the gradients of
    the mean loss over the global batch, synchronized over the data axes by
    ``tc.grad_sync``, and the step's metrics."""
    loss_fn = make_loss_fn(tc, constrain="full" if tc.grad_sync == "auto"
                           else "none")

    if tc.grad_sync == "auto":
        def auto_grads(params, batch):
            k = tc.microbatches
            if k <= 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                return grads, metrics
            mb = jax.tree.map(
                lambda v: v.reshape((k, v.shape[0] // k) + v.shape[1:]),
                batch)

            def mb_step(acc, one):
                g_acc, m_acc = acc
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, one)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), g_acc, grads)
                m_acc = jax.tree.map(lambda a, m: a + m / k, m_acc,
                                     metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            m0 = {"loss": jnp.zeros((), jnp.float32),
                  "accuracy": jnp.zeros((), jnp.float32),
                  "aux_loss": jnp.zeros((), jnp.float32)}
            (grads, metrics), _ = jax.lax.scan(mb_step, (g0, m0), mb)
            grads = jax.tree.map(lambda g, p: (g / k).astype(p.dtype),
                                 grads, params)
            return grads, metrics
        return auto_grads

    if tc.grad_sync not in EXPLICIT_MODES:
        raise ValueError(f"unknown grad_sync {tc.grad_sync}")
    if mesh is None:
        raise ValueError("explicit grad_sync modes need a mesh")

    inner = dp_axes[-1]                   # tree axis (intra-pod)
    outer = dp_axes[0] if len(dp_axes) > 1 else None
    axis_size = mesh.shape[inner]
    mode = {"canary": "canary", "canary_fp": "canary", "ring": "ring",
            "hierarchical": "hierarchical"}[tc.grad_sync]
    fixed_point = tc.grad_sync == "canary_fp"
    model_axis = next((a for a in mesh.axis_names if a not in dp_axes), None)
    roots = list(tc.canary_roots) if tc.canary_roots is not None else None

    def local_grads(params, batch):
        """Per-data-shard gradients + explicit Canary reduction."""
        import dataclasses as _dc
        from repro.parallel.context import (get_parallel_context,
                                            parallel_context)
        ctx = get_parallel_context()
        if ctx is not None and ctx.constrain_activations:
            # data axes are manual inside this shard_map: activation
            # constraints must not mention them
            with parallel_context(_dc.replace(ctx, constrain_activations=False,
                                              allow_shardmap_layers=False)):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        # the grads follow the params: replicated over the data axes,
        # split over the model axis as the Trainer places them
        specs = param_specs(grads, mesh, fsdp=None, model=model_axis,
                            use_fsdp=False) if model_axis else None
        with jax.named_scope("grad_sync"):
            synced = canary_allreduce_tree(
                grads, axis_name=inner, axis_size=axis_size, roots=roots,
                num_blocks=tc.canary_blocks, mode=mode, outer_axis=outer,
                fixed_point=fixed_point, leaf_specs=specs)
            # average over the data parallelism degree
            dp = axis_size * (mesh.shape[outer] if outer else 1)
            synced = jax.tree.map(lambda g: g / dp, synced)
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(jax.lax.pmean(m, inner), outer)
            if outer else jax.lax.pmean(m, inner), metrics)
        return synced, metrics

    batch_in_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])

    def explicit_grads(params, batch):
        return jax.shard_map(
            local_grads,
            mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: batch_in_spec, batch)),
            out_specs=(P(), P()),
            axis_names=set(dp_axes),
            check_vma=False,
        )(params, batch)

    return explicit_grads


def make_train_step(tc: TrainConfig, mesh: Optional[Mesh] = None,
                    dp_axes: Tuple[str, ...] = ("data",)) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). jit/lower is the caller's job (launcher / dryrun)."""
    grads_fn = make_grads_fn(tc, mesh, dp_axes)

    def train_step(params, opt_state, batch):
        grads, metrics = grads_fn(params, batch)
        with jax.named_scope("optimizer"):
            params, opt_state, om = adamw_update(grads, opt_state, params,
                                                 tc.optimizer)
        metrics.update(om)
        return params, opt_state, metrics

    return train_step


def init_train_state(tc: TrainConfig, key) -> Tuple[Any, AdamWState]:
    from repro.models import init_params
    params = init_params(tc.model, key)
    return params, adamw_init(params, tc.optimizer)
