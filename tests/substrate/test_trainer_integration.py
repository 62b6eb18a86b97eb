"""Trainer integration: end-to-end loops, checkpoint-resume determinism,
and the congestion-oracle replan path (subprocess with 8 devices)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.data import DataConfig
from repro.models import get_config
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer, TrainerConfig


def _trainer(steps=6, ckpt=None, every=0):
    cfg = get_config("llama3.2-1b", "smoke")
    tc = TrainConfig(model=cfg, optimizer=AdamWConfig(lr=1e-3))
    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=32)
    return Trainer(TrainerConfig(train=tc, data=data, steps=steps,
                                 log_every=0, checkpoint_dir=ckpt,
                                 checkpoint_every=every))


def test_trainer_runs_and_learns():
    t = _trainer(steps=8)
    hist = t.run()
    assert len(hist) == 8
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_checkpoint_resume_exact(tmp_path):
    """Deterministic data + checkpointing => resumed run matches unbroken."""
    d = str(tmp_path / "ck")
    t1 = _trainer(steps=6, ckpt=d, every=3)
    h1 = t1.run()

    # resume from step 3 and replay steps 3..5
    from repro.checkpoint import restore_checkpoint
    t2 = _trainer(steps=6)
    like_p = t2.params
    like_o = t2.opt_state
    params, opt, step = restore_checkpoint(d, 3, like_p, like_o)
    t2.params, t2.opt_state = params, opt
    from repro.data import batch_at
    import jax.numpy as jnp
    losses = []
    for s in range(3, 6):
        batch = {k: jnp.asarray(v) for k, v in
                 batch_at(t2.cfg.data, s).items()}
        t2.params, t2.opt_state, m = t2.step_fn(t2.params, t2.opt_state,
                                                batch)
        losses.append(float(m["loss"]))
    want = [h["loss"] for h in h1[3:6]]
    np.testing.assert_allclose(losses, want, rtol=1e-4, atol=1e-5)


def test_microbatched_step_matches_full_batch():
    """k microbatches must produce the same update as one full batch."""
    import jax.numpy as jnp
    from repro.optim import init as adamw_init
    from repro.train import make_train_step
    from repro.models import init_params
    cfg = get_config("llama3.2-1b", "smoke").with_(dtype="float32")
    oc = AdamWConfig(lr=1e-3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params, oc)
    from repro.data import batch_at
    batch = {k: jnp.asarray(v) for k, v in batch_at(
        DataConfig(vocab_size=cfg.vocab_size, global_batch=8, seq_len=16),
        0).items()}
    s1 = jax.jit(make_train_step(TrainConfig(model=cfg, optimizer=oc)))
    s4 = jax.jit(make_train_step(TrainConfig(model=cfg, optimizer=oc,
                                             microbatches=4)))
    p1, o1, m1 = s1(params, opt, batch)
    p4, o4, m4 = s4(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        # fp32 accumulation order differs: allow reassociation-level noise
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-3, atol=1e-4)


def test_replan_keeps_every_train_config_field():
    """An oracle re-plan changes the Canary roots and nothing else of the
    TrainConfig (microbatches, z_loss, ... survive), and a Trainer given a
    mesh holds its params and batches on that mesh."""
    import dataclasses

    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import replan

    mesh = make_host_mesh(1, 1)
    cfg = get_config("llama3.2-1b", "smoke")
    tc = TrainConfig(model=cfg, optimizer=AdamWConfig(lr=1e-3),
                     grad_sync="canary", canary_blocks=8, microbatches=2,
                     z_loss=1e-4)
    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=16)
    t = Trainer(TrainerConfig(train=tc, data=data, steps=0), mesh=mesh)
    t.oracle.feedback(1.0)
    assert replan(tc, t.oracle) == dataclasses.replace(
        tc, canary_roots=tuple(t.oracle.plan()))
    assert replan(tc, None) is tc
    for leaf in jax.tree.leaves((t.params, t.opt_state,
                                 t.make_batch(0))):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh == mesh


REPLAN_SCRIPT = r"""
import os
import jax
from repro.data import DataConfig
from repro.models import get_config
from repro.optim import AdamWConfig
from repro.parallel.context import ParallelContext, parallel_context
from repro.train import TrainConfig, Trainer, TrainerConfig

cfg = get_config("llama3.2-1b", "smoke")
mesh = jax.make_mesh((8, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
tc = TrainConfig(model=cfg, optimizer=AdamWConfig(lr=1e-3),
                 grad_sync="canary", canary_blocks=8)
data = DataConfig(vocab_size=cfg.vocab_size, global_batch=8, seq_len=32)
ctx = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model")
with parallel_context(ctx):
    t = Trainer(TrainerConfig(train=tc, data=data, steps=8, log_every=0,
                              replan_every=3), mesh=mesh)
    hist = t.run()
assert t.oracle is not None and len(t.oracle._history) > 0
assert all(h["loss"] == h["loss"] for h in hist)
print("REPLAN_OK", hist[0]["loss"], "->", hist[-1]["loss"])
"""


def test_canary_trainer_with_oracle_replan():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.run([sys.executable, "-c", REPLAN_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=root)
    assert "REPLAN_OK" in proc.stdout, proc.stdout + "\n" + proc.stderr
