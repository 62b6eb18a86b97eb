"""Training loop: data pipeline + train_step + congestion-oracle feedback +
checkpointing. Given a mesh, the Trainer places params, optimizer state and
every batch on it with the rules of ``repro.parallel.sharding``; without one
everything stays on the default device.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.collective import CongestionOracle
from repro.data import DataConfig, batch_at
from repro.optim import AdamWState
from repro.parallel.sharding import batch_spec, param_shardings
from .train_step import TrainConfig, init_train_state, make_train_step


@dataclass
class TrainerConfig:
    train: TrainConfig
    data: DataConfig
    steps: int = 50
    log_every: int = 10
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    replan_every: int = 0     # >0: re-plan canary roots from oracle feedback


def replan(tc: TrainConfig,
           oracle: Optional[CongestionOracle]) -> TrainConfig:
    """``tc`` with the oracle's current Canary roots, every other field
    kept; ``tc`` itself when there is no oracle."""
    if oracle is None:
        return tc
    return dataclasses.replace(tc, canary_roots=tuple(oracle.plan()))


class Trainer:
    def __init__(self, cfg: TrainerConfig, mesh=None, dp_axes=("data",),
                 model_axis="model", seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.dp_axes = dp_axes
        self.model_axis = model_axis
        init = partial(init_train_state, cfg.train)
        key = jax.random.PRNGKey(seed)
        self.batch_sharding = None
        if mesh is None:
            self.params, self.opt_state = init(key)
        else:
            # explicit grad-sync modes reduce over the data axes themselves,
            # so they need params replicated there; auto shards them (FSDP)
            dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            p_shard = param_shardings(
                jax.eval_shape(init, key)[0], mesh, fsdp=dp,
                model=model_axis, use_fsdp=cfg.train.grad_sync == "auto")
            state_shard = (p_shard, AdamWState(
                step=NamedSharding(mesh, P()), m=p_shard, v=p_shard))
            self.params, self.opt_state = jax.jit(
                init, out_shardings=state_shard)(key)
            self.batch_sharding = NamedSharding(
                mesh, batch_spec(mesh, cfg.data.global_batch, dp))
        self.oracle: Optional[CongestionOracle] = None
        if cfg.train.grad_sync in ("canary", "canary_fp") and mesh is not None:
            self.oracle = CongestionOracle(
                axis_size=mesh.shape[dp_axes[-1]],
                num_blocks=cfg.train.canary_blocks)
        self._build_step()
        self.history: List[Dict[str, float]] = []

    def _build_step(self):
        tc = replan(self.cfg.train, self.oracle)
        fn = make_train_step(tc, mesh=self.mesh, dp_axes=self.dp_axes)
        self.step_fn = jax.jit(fn, donate_argnums=(0, 1))

    def make_batch(self, step: int) -> Dict[str, jnp.ndarray]:
        """The batch of ``step``, split over the data axes of the mesh when
        the Trainer has one. Runs under the host span ``make_batch``."""
        with jax.profiler.TraceAnnotation("make_batch"):
            np_batch = batch_at(self.cfg.data, step)
            batch = {k: jax.device_put(v, self.batch_sharding)
                     for k, v in np_batch.items()}
        mcfg = self.cfg.train.model
        B = self.cfg.data.global_batch
        if mcfg.frontend == "audio_stub":
            batch["frames"] = 0.02 * jnp.ones(
                (B, mcfg.encoder_seq, mcfg.d_model), jnp.dtype(mcfg.dtype))
        if mcfg.frontend == "vision_stub":
            batch["patches"] = 0.02 * jnp.ones(
                (B, mcfg.num_patches, mcfg.d_model), jnp.dtype(mcfg.dtype))
        return batch

    def run(self) -> List[Dict[str, float]]:
        cfg = self.cfg
        for step in range(cfg.steps):
            batch = self.make_batch(step)
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            dt = time.perf_counter() - t0
            metrics["step"] = step
            metrics["step_time_s"] = dt
            self.history.append(metrics)
            if self.oracle is not None:
                self.oracle.feedback(dt)
                if cfg.replan_every and (step + 1) % cfg.replan_every == 0:
                    self._build_step()   # adopt the re-planned roots
            if cfg.log_every and step % cfg.log_every == 0:
                print(f"step {step:5d} loss {metrics['loss']:.4f} "
                      f"acc {metrics.get('accuracy', 0):.4f} {dt*1e3:.0f}ms")
            if cfg.checkpoint_dir and cfg.checkpoint_every and \
                    (step + 1) % cfg.checkpoint_every == 0:
                from repro.checkpoint import save_checkpoint
                save_checkpoint(cfg.checkpoint_dir, step + 1, self.params,
                                self.opt_state)
        return self.history
