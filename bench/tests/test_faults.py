"""A whole run, the look for a chip skipped, with the timed path broken
underneath: ``correct`` has to come out false for each fault that a
training cell can have, and true for the sound program.

Runs on the CPU at the sizes of ``tiny``, with the cells' own limits.
"""
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.tests import tiny

ONE = "mamba2-130m.b8x2048.1chip"
FOUR = "mamba2-130m.dp4-canary.b8x2048.4chip"


def _break_step(monkeypatch, how):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def make(tc, mesh=None, dp_axes=("data",)):
        step = real(tc, mesh=mesh, dp_axes=dp_axes)
        if how == "unchanged":        # a step that returns its state as is
            def broken(params, opt_state, batch):
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics
        else:                         # half of the batch left out
            def broken(params, opt_state, batch):
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return step(params, opt_state, half)
        return broken

    monkeypatch.setattr(trainer_mod, "make_train_step", make)


@pytest.mark.parametrize("name", spec.cell_names(1))
def test_sound_program_is_correct(name):
    assert tiny.run(tiny.cell(name))["correct"] is True


@pytest.mark.parametrize("how", ["unchanged", "half"])
def test_broken_step_is_not_correct(monkeypatch, how):
    _break_step(monkeypatch, how)
    out = tiny.run(tiny.cell(ONE))
    assert out["correct"] is False, out["check"]


def _exchange_left_out():
    """In a child with 4 CPU devices: canary without the exchange."""
    import repro.train.train_step as ts
    ts.canary_allreduce_tree = lambda grads, **kw: grads
    out = tiny.run(tiny.cell(FOUR))
    print("CORRECT", out["correct"], out["check"])


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    p = subprocess.run(
        [sys.executable, "-c", "from bench.tests import test_faults as t; "
         "t._exchange_left_out()"], cwd=root, env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CORRECT False" in p.stdout, p.stdout[-2000:]
