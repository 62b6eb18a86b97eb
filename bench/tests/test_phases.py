"""The step's phases against a small recorded trace and the HLO of its step
(``data/phases_trace.pbtxt``, ``data/phases_step.hlo``; times in ns).

Chip 0 runs two step programs back to back, [1000, 6000) and [6000,
11000). Each step: a ``while`` of the forward pass spans [0, 1000) of the
step and holds a forward op (600) and an op of its body without metadata
(400); then a backward op (1000) and a recompute op (500); grad sync, a
collective-permute's start (100), a packing
fusion (400) and its done (500); the metrics' all-reduce (100), a copy
without metadata (100), the optimizer (800), an op the HLO does not name
(100), and 400 idle. Chip 1 runs two steps, [1000, 6000) and [7000,
12000), each with two overlapping forward ops covering 1500, a
synchronous collective-permute of grad sync (500) and the optimizer
(2500); a backward op between its steps belongs to no step. The host runs
``make_batch`` twice inside ``bench_window`` (200 and 300) and twice
outside it.
"""
import os

import pytest

from bench import phases, trace
from bench.harness import RunRecord, STEP_PROGRAM
from bench.metrics import (backward_ms, forward_ms, grad_sync_ms,
                           grad_sync_ops, idle_share, input_ms,
                           optimizer_ms, recompute_ms)

DATA = os.path.join(os.path.dirname(__file__), "data")
DEVICE_METRICS = (forward_ms, backward_ms, recompute_ms, optimizer_ms,
                  grad_sync_ms, grad_sync_ops)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "phases_trace.pbtxt")) as f:
        tr = trace.from_profile(ProfileData.from_text_proto(f.read()))
    with open(os.path.join(DATA, "phases_step.hlo")) as f:
        text = f.read()
    return tr, trace.device_windows(tr, STEP_PROGRAM), text


def _run(recorded, text=None, chips=(0, 1)):
    tr, ws, hlo_text = recorded
    calls = []

    def step_hlo():
        calls.append(1)
        return hlo_text if text is None else text

    run = RunRecord(cell=None, seed=0, chips=len(chips), peak={},
                    flops_per_step=0.0, trace=tr,
                    windows={c: ws[c] for c in chips}, step_hlo=step_hlo)
    return run, calls


def test_instructions_carry_their_phases_and_opcode(recorded):
    ins = phases.instructions(recorded[2])
    assert ins["collective-permute-start.1"] == phases.Instr(
        ("grad_sync",), "collective-permute-start")
    assert ins["fusion.3"] == phases.Instr(("backward", "recompute"),
                                           "fusion")
    assert ins["copy.1"] == phases.Instr((), "copy")
    assert ins["p.1"] == phases.Instr((), "parameter")
    assert ins["all-reduce.1"] == phases.Instr((), "all-reduce")
    assert ins["multiply.9"].opcode == "multiply"     # inside a fusion
    assert "FileNames" not in ins


def test_an_op_without_a_scope_takes_its_callers(recorded):
    """The windowed sum in the forward ``while``'s body has no metadata:
    it is forward, as its loop is."""
    ins = phases.instructions(recorded[2])
    assert ins["reduce-window.1"] == phases.Instr(("forward",),
                                                  "reduce-window")


@pytest.mark.parametrize("op_name, want", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/mul",
     ("forward",)),
    ("jit(train_step)/shard_map/transpose(jvp(forward))/dot_general",
     ("backward",)),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/mul", ("backward", "recompute")),
    ("jit(train_step)/optimizer/mul", ("optimizer",)),
    ("jit(train_step)/shard_map/grad_sync/jit(remainder)/rem",
     ("grad_sync",)),
    ("jit(train_step)/shard_map/psum", ()),
    # the parent's names: no scope, so no phase
    ("jit(train_step)/shard_map/transpose(jvp())/while", ()),
    ("jit(train_step)/jvp()/mul", ()),
    ("", ()),
])
def test_phases_of_a_name_stack(op_name, want):
    assert phases.phases_of(op_name) == want


def test_union_per_phase_per_step_averaged_over_chips(recorded):
    run, _ = _run(recorded)
    r = phases.of(run)
    assert r.ns == pytest.approx({
        "forward": (1000 + 1500) / 2,        # chip 1's two ops overlap
        "backward": (1500 + 0) / 2,
        "recompute": (500 + 0) / 2,
        "grad_sync": (1000 + 500) / 2,
        "optimizer": (800 + 2500) / 2,
        "unattributed": (300 + 0) / 2})
    assert forward_ms.read(run) == pytest.approx(1250 / 1e6)
    assert backward_ms.read(run) == pytest.approx(750 / 1e6)
    assert optimizer_ms.read(run) == pytest.approx(1650 / 1e6)
    assert grad_sync_ms.read(run) == pytest.approx(750 / 1e6)


def test_recompute_is_part_of_backward(recorded):
    run, _ = _run(recorded)
    assert 0 < recompute_ms.read(run) < backward_ms.read(run)
    assert recompute_ms.read(run) == pytest.approx(250 / 1e6)


def test_grad_sync_ops_count_an_async_pair_once(recorded):
    run, _ = _run(recorded)
    # chip 0: start and done of one permute; chip 1: one synchronous one;
    # the metrics' all-reduce is not grad sync
    assert grad_sync_ops.read(run) == pytest.approx(1.0)
    assert phases.of(run).collectives["unattributed"] == pytest.approx(0.5)


def test_one_chip_phases_and_idle_make_the_step(recorded):
    run, _ = _run(recorded, chips=(0,))
    r = phases.of(run)
    w = run.windows[0]
    steps = len(w.steps)
    idle = idle_share.read(run) / 100 * w.window_ns / steps
    # recompute is a part of backward, so it is not added again
    attributed = sum(r.ns[p] for p in ("forward", "backward", "grad_sync",
                                       "optimizer"))
    assert attributed + r.ns["unattributed"] + idle == pytest.approx(
        trace.step_period_ns(w))
    assert r.ns["unattributed"] == pytest.approx(300)


def test_hlo_compiled_and_parsed_once_per_run(recorded):
    run, calls = _run(recorded)
    for m in DEVICE_METRICS:
        assert m.read(run) is not None
    assert len(calls) == 1


def test_a_step_without_scopes_reads_nothing(recorded):
    """The parent's program names no phase: every device metric is left
    out, and none raises."""
    unscoped = "\n".join(
        line.replace("forward", "").replace("grad_sync/", "")
        .replace("optimizer/", "") for line in recorded[2].splitlines())
    run, _ = _run(recorded, text=unscoped)
    assert [m.read(run) for m in DEVICE_METRICS] == [None] * 6
    assert phases.of(run).ns["unattributed"] > 0


def test_untraced_run_reads_nothing():
    run = RunRecord(cell=None, seed=0, chips=1, peak={}, flops_per_step=0.0)
    assert [m.read(run) for m in DEVICE_METRICS + (input_ms,)] == [None] * 7


def test_input_ms_reads_make_batch_inside_the_window(recorded):
    run, _ = _run(recorded)
    assert input_ms.read(run) == pytest.approx(250 / 1e6)


def test_make_batch_leaves_a_host_span(tmp_path):
    """``Trainer.make_batch`` on the CPU, under the profiler, leaves a
    ``make_batch`` host event that ``bench.trace.load`` reads."""
    import jax
    from repro.data import DataConfig
    from repro.models import ModelConfig
    from repro.train import TrainConfig, Trainer, TrainerConfig
    from bench.reference import dense

    model = ModelConfig(**dense.TINY)
    cfg = TrainerConfig(train=TrainConfig(model=model),
                        data=DataConfig(vocab_size=model.vocab_size,
                                        global_batch=2, seq_len=16),
                        steps=0, log_every=0)
    trainer = Trainer(cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            jax.block_until_ready(trainer.make_batch(0))
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert "make_batch" in {e.name for e in tr.host}
    run = RunRecord(cell=None, seed=0, chips=1, peak={}, flops_per_step=0.0,
                    trace=tr)
    assert input_ms.read(run) > 0
