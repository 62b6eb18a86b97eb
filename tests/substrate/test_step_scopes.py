"""The training step's named scopes in its compiled HLO.

``train_step`` names its forward pass (``forward``; the backward pass then
carries ``transpose(jvp(forward))`` and remat's recompute
``rematted_computation`` under it), the explicit modes' gradient reduction
(``grad_sync``) and the optimizer (``optimizer``). The benchmark reads a
phase's device time by these names, so a JAX that renamed them would fail
here rather than let a metric read nothing. Scopes are metadata only: with
``jax.named_scope`` replaced by a null context the instructions are the
same.

One child process with 4 CPU devices compiles a tiny mamba2 step on a
4-way ``canary`` data mesh and a tiny dense step on one device, each with
and without the scopes (the widths of ``bench/tests/tiny.py``).
"""
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = r"""
import contextlib
import os
import sys

import jax
import pytest

from bench.tests.tiny import MODELS
from repro.data import DataConfig
from repro.models import ModelConfig
from repro.parallel.context import ParallelContext, parallel_context
from repro.train import TrainConfig, Trainer, TrainerConfig


class NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def step_hlo(family, grad_sync, chips, batch):
    model = ModelConfig(**MODELS[family])
    cfg = TrainerConfig(
        train=TrainConfig(model=model, grad_sync=grad_sync),
        data=DataConfig(vocab_size=model.vocab_size, global_batch=batch,
                        seq_len=64), steps=0, log_every=0)
    if chips == 1:
        t = Trainer(cfg)
        return t.step_fn.lower(t.params, t.opt_state,
                               t.make_batch(0)).compile().as_text()
    mesh = jax.make_mesh((chips, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:chips])
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model")
    with parallel_context(ctx):
        t = Trainer(cfg, mesh=mesh)
        return t.step_fn.lower(t.params, t.opt_state,
                               t.make_batch(0)).compile().as_text()


out = sys.argv[1]
for scoped in (True, False):
    with pytest.MonkeyPatch.context() as mp:
        if not scoped:
            mp.setattr(jax, "named_scope", lambda name: NoScope())
        for name, args in (("canary", ("mamba2", "canary", 4, 4)),
                           ("dense", ("dense", "auto", 1, 2))):
            text = step_hlo(*args)
            tag = "scoped" if scoped else "plain"
            with open(os.path.join(out, f"{name}.{tag}.hlo"), "w") as f:
                f.write(text)
"""

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?\S+ = ")
METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVE = re.compile(r"\s((?:all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)(?:-start|-done)?)\(")


@pytest.fixture(scope="module")
def hlo(tmp_path_factory):
    out = tmp_path_factory.mktemp("step_hlo")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(out)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return {f.stem: f.read_text() for f in out.iterdir()}


def _instructions(text):
    return [line for line in text.splitlines() if INSTRUCTION.match(line)]


def _op_names(text):
    return [m.group(1) for line in _instructions(text)
            for m in [OP_NAME.search(line)] if m]


@pytest.mark.parametrize("step", ["canary", "dense"])
def test_each_scope_tags_an_instruction(hlo, step):
    names = _op_names(hlo[f"{step}.scoped"])
    want = ["jvp(forward)", "transpose(jvp(forward))",
            "rematted_computation", "optimizer"]
    if step == "canary":
        want.append("grad_sync")
    for scope in want:
        assert any(scope in n.split("/") for n in names), scope
    # jvp(forward) on its own, not only inside transpose(...)
    assert any("/jvp(forward)/" in n and "transpose(" not in n
               for n in names)


def test_every_grad_collective_is_under_grad_sync(hlo):
    """Only the metrics' pmean (an all-reduce named ``psum``) lies outside
    ``grad_sync``; the tree's collective-permutes, 11 gradient leaves x
    2 log2(4) rounds, all lie inside."""
    permutes, others = 0, []
    for line in _instructions(hlo["canary.scoped"]):
        m = COLLECTIVE.search(line)
        if not m:
            continue
        name = OP_NAME.search(line).group(1)
        if "/grad_sync/" in name:
            if m.group(1) in ("collective-permute",
                              "collective-permute-done"):
                permutes += 1
        else:
            others.append((m.group(1), name))
    assert permutes == 44
    assert others and all(op.startswith("all-reduce")
                          and name.endswith("/psum") for op, name in others)


@pytest.mark.parametrize("step", ["canary", "dense"])
def test_scopes_change_no_instruction(hlo, step):
    scoped = [METADATA.sub("", line)
              for line in _instructions(hlo[f"{step}.scoped"])]
    plain = [METADATA.sub("", line)
             for line in _instructions(hlo[f"{step}.plain"])]
    assert scoped == plain
    assert not any("forward" in n for n in _op_names(hlo[f"{step}.plain"]))
