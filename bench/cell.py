"""One training cell on the chip: the Trainer as ``repro.launch.train``
builds it, its first steps, and the timed window.

``prepare`` builds the cell's Trainer, puts the benchmark's weights in
place of its own (made on the device from the seed, in the types the
program serves them in, placed as the Trainer placed its own), and drives
it through the steps that ``check`` compares. Every step, compared or
timed, goes through ``drive``: the Trainer's own batch (``make_batch``) and
compiled step (``step_fn``), as ``Trainer.run`` takes them, but with a few
seconds of steps dispatched ahead of the one waited for, so that a host
that stands still for a moment leaves the chip fed. The compared steps
compile every program the window runs. ``window`` then drives steps for
the requested seconds and waits for all that it sent.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from bench import check, data
from bench.reference import family
from bench.reference.numerics import rounded
from bench.spec import Cell


@dataclass
class Prepared:
    trainer: object
    readings: check.Readings
    step_s: float               # host-clock time a step of the last set-up call


def build(cell: Cell, seed: int, devices: List):
    """The cell's Trainer and its parallel context, as the launcher makes
    them: a (chips, 1) mesh over ("data", "model")."""
    from repro.data import DataConfig
    from repro.models import ModelConfig
    from repro.optim import AdamWConfig
    from repro.parallel.context import ParallelContext
    from repro.train import TrainConfig, TrainerConfig

    wl = cell.workload
    if wl.get("data_parallel", 1) != cell.chips:
        raise ValueError(f"{cell.name}: data_parallel "
                         f"{wl.get('data_parallel')} on {cell.chips} chips")
    model = ModelConfig(**cell.model)
    mesh = jax.make_mesh((cell.chips, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[:cell.chips])
    tc = TrainConfig(model=model, optimizer=AdamWConfig(**wl["optimizer"]),
                     grad_sync=wl["grad_sync"],
                     canary_blocks=wl.get("canary_blocks", 16))
    dc = DataConfig(vocab_size=model.vocab_size,
                    global_batch=wl["global_batch"], seq_len=wl["seq_len"],
                    seed=data.stream_seed(seed, 0))
    cfg = TrainerConfig(train=tc, data=dc, steps=1, log_every=0,
                        replan_every=0)
    ctx = ParallelContext(mesh=mesh, data_axes=("data",), model_axis="model")
    return cfg, mesh, ctx


def _served_init(model: Dict, fam, key):
    return jax.tree.map(lambda x, t: x.astype(t), fam.init(model, key),
                        fam.served_dtypes(model))


def _change_norms(model: Dict, fam, params, key):
    p0 = jax.tree.map(rounded, fam.init(model, key), fam.served_dtypes(model))
    return check.leaf_norms(jax.tree.map(
        lambda p, p0: p.astype(jnp.float32) - p0, params, p0))


AHEAD_S = 4.0          # seconds of steps dispatched ahead of the one waited for


@dataclass
class Drive:
    metrics: List[Dict]          # each step's metrics, as floats
    seconds: float               # first dispatch to the end of the wait
    start: float                 # perf_counter at the first dispatch
    dispatch_s: List[float]      # host time from one dispatch to the next


def drive(trainer, stream: int, ahead: int, steps: int = 0,
          seconds: float = 0.0) -> Drive:
    """``steps`` steps on data stream ``stream``, or as many as are
    dispatched in ``seconds``; at most ``ahead`` are in flight when the
    next is dispatched. Sends nothing more when the steps or the seconds
    are done, waits for the parameters, the optimizer state and every
    step's metrics, and reads the clock after that wait."""
    trainer.cfg.data = dataclasses.replace(trainer.cfg.data, seed=stream)
    pending, metrics, dispatch_s = deque(), [], []
    t0 = last = time.perf_counter()
    step = 0
    while step < steps if steps else time.perf_counter() - t0 < seconds:
        if len(pending) >= ahead:
            metrics.append({k: float(v) for k, v in pending.popleft().items()})
        batch = trainer.make_batch(step)
        trainer.params, trainer.opt_state, m = trainer.step_fn(
            trainer.params, trainer.opt_state, batch)
        pending.append(m)
        now = time.perf_counter()
        dispatch_s.append(now - last)
        last = now
        step += 1
    jax.block_until_ready((trainer.params, trainer.opt_state, list(pending)))
    t1 = time.perf_counter()
    metrics += [{k: float(v) for k, v in m.items()} for m in pending]
    return Drive(metrics, t1 - t0, t0, dispatch_s)


def prepare(cell: Cell, seed: int, devices: List) -> Prepared:
    """Build the cell and take the compared steps. The cell's parallel
    context stays installed until ``free``."""
    from repro.parallel.context import set_parallel_context
    from repro.train import Trainer
    cfg, mesh, ctx = build(cell, seed, devices)
    set_parallel_context(ctx)
    trainer = Trainer(cfg, mesh=mesh, seed=seed % (1 << 31))
    fam = family(cell.config["reference"])
    model = cell.model
    key = check.seed_key(seed)
    want = jax.eval_shape(partial(_served_init, model, fam), key)
    have = jax.eval_shape(lambda t: t, trainer.params)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise ValueError(f"{cell.name}: the reference's weights do not match "
                         f"the program's layout:\n{want}\n{have}")
    shardings = jax.tree.map(lambda x: x.sharding, trainer.params)
    for x in jax.tree.leaves(trainer.params):
        x.delete()
    trainer.params = jax.jit(partial(_served_init, model, fam),
                             out_shardings=shardings)(key)

    opt = cell.workload["optimizer"]
    hist = []
    for call, steps in enumerate(check.CHECK_CALLS):
        d = drive(trainer, data.stream_seed(seed, call), steps, steps=steps)
        hist += d.metrics
        if call == 0:
            gnorm = hist[0]["grad_norm"]
            clip = opt["grad_clip"]
            scale = min(1.0, clip / max(gnorm, 1e-9)) if clip > 0 else 1.0
            m1 = check.norms(trainer.opt_state.m)
            grad = {k: v / ((1 - opt["b1"]) * scale) for k, v in m1.items()}
    change = dict(zip(check.leaf_names(trainer.params), (
        float(v) for v in jax.device_get(jax.jit(
            partial(_change_norms, model, fam))(trainer.params, key)))))
    readings = check.Readings([h["loss"] for h in hist], grad, change)
    return Prepared(trainer, readings, d.seconds / len(d.metrics))


TRACE_SECONDS = 10.0   # a traced window runs at most this long


@dataclass
class Window:
    steps: int
    tokens: int
    seconds: float
    start: float                 # perf_counter at the first timed dispatch
    nonfinite: int
    ahead: int                   # steps in flight at most
    dispatch_s: List[float]      # host time from one dispatch to the next


def window(prep: Prepared, cell: Cell, seed: int, seconds: float,
           trace_dir: Optional[str] = None) -> Window:
    """Steps dispatched for about ``seconds``, waited for to the last;
    traced to ``trace_dir`` when given, for at most ``TRACE_SECONDS``."""
    if trace_dir:
        seconds = min(seconds, TRACE_SECONDS)
    ahead = max(1, math.ceil(AHEAD_S / max(prep.step_s, 1e-3)))
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        d = drive(prep.trainer, data.stream_seed(seed, len(check.CHECK_CALLS)),
                  ahead, seconds=seconds)
    if trace_dir:
        jax.profiler.stop_trace()
    wl = cell.workload
    steps = len(d.metrics)
    return Window(steps=steps, tokens=steps * wl["global_batch"] *
                  wl["seq_len"], seconds=d.seconds, start=d.start,
                  nonfinite=sum(not math.isfinite(h["loss"])
                                for h in d.metrics),
                  ahead=ahead, dispatch_s=d.dispatch_s)


def step_hlo(trainer) -> str:
    """Optimized HLO of the Trainer's compiled step."""
    batch = trainer.make_batch(0)
    return trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                 batch).compile().as_text()


def free(prep: Prepared) -> None:
    """Delete the program's state, so that the reference has the chip."""
    from repro.parallel.context import set_parallel_context
    set_parallel_context(None)
    t = prep.trainer
    for x in jax.tree.leaves((t.params, t.opt_state)):
        x.delete()
    t.params = t.opt_state = None
    prep.trainer = None
