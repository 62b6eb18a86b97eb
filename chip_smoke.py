#!/usr/bin/env python3
"""Smoke run of the device half on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # grad sync on a (4, 1) mesh, nothing else

One chip, in order:

1. device check: JAX must see a TPU; anything else exits non-zero at once;
2. training: ``repro.launch.train.main`` trains mamba2-130m at its published
   widths (24 layers, d_model 768, vocab 50280), batch 8 x seq 2048, for 5
   steps with ``--grad-sync auto`` and 2 with ``--grad-sync canary_fp`` (on
   one chip the tree degenerates, but the fixed-point kernels run compiled
   inside the step). Every loss must be finite. The loss of a 1 x 512 slice
   of the first batch, at the model's bfloat16 on the chip, must agree with
   the same forward pass in float32 on the host's CPU backend;
3. trace replay: two CANARY runs of the packet engine with different seeds
   and timeouts are recorded on the host, and ``fixed_point_replay`` runs
   both on the chip. The int32 results must be bit-identical and match
   ``reference_allreduce`` within the quantization tolerance;
4. flow solve: the flow backend's jitted solve of the fig7 matrix on
   ``fat_tree_1024`` must match its host mirror ``solve_cell``.

With ``--chips 4``: the synced gradients of one mamba2-130m step, in
float32 at the highest matmul precision, under ``canary``, ``ring`` and
``canary_fp`` are compared with ``auto``'s, ``canary_fp`` must give the same
bits under two root plans, and the trainer then takes 3 bfloat16 steps with
``--grad-sync canary --data-parallel 4``. Beside them, unchecked, the same
gradients in the model's bfloat16: ``auto`` with FSDP-sharded and with
replicated params, and ``canary``.

Every phase prints ``smoke reading`` lines: what it found, not benchmark
metrics. The last line of standard output is one JSON object naming the
device; it is printed only when every phase passed. The script starts no
other process: the chip belongs to this one.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

ARCH = "mamba2-130m"
VARIANT = "full"           # published widths
BATCH = 8
SEQ = 2048                 # Mamba-2's training context
AUTO_STEPS = 5
FP_STEPS = 2
REF_SEQ = 512              # CPU reference slice: 1 x REF_SEQ of batch 0
SEED = 0
# bfloat16 keeps 8 significant bits, so every rounding is within 2**-9 of
# the value. The loss is a mean over 512 tokens of a 24-layer forward in
# bfloat16 against the same pass in float32: rounding errors of single
# tokens average out, and a gap above 1% means a wrong result, not rounding.
LOSS_RTOL = 1e-2
# float32 keeps 24 significant bits (6e-8 relative); the flow solve is a few
# operations per cell against the host's float64 mirror.
FLOW_RTOL = 1e-5
REPLAY_HOSTS = 16          # participants: 25% of a 64-host fat tree
REPLAY_BYTES = 256 * 1024
FLOW_TOPOLOGY = "fat_tree_1024"
FLOW_REPS = 2
# The gradient comparison runs the model in float32 at the highest matmul
# precision. In bfloat16 its gradients move by far more than a sum's
# rounding under a mere change of partitioning (on a v5e, 0.85 relative L2
# between auto with FSDP-sharded params and the explicit modes; on the CPU
# backend up to 1.13 of a leaf's largest value), which would hide a wrong
# reduction. In float32 two partitionings differ by at most 1.5e-3 of a
# leaf's largest value on the CPU backend, while a lost or doubled shard
# moves the sum by a quarter or more.
GRAD_DTYPE = "float32"
GRAD_REL_L2 = 1e-3
GRAD_LEAF_MAX = 1e-2
GRAD_DP = 4
TRAIN_4CHIP_STEPS = 3


class SmokeFailure(Exception):
    pass


def reading(phase: str, **values) -> None:
    print(f"smoke reading | {phase} | {json.dumps(values, default=float)}",
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# ---------------------------------------------------------------- phases
def phase_device(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices)}
    check(d.platform == "tpu", f"JAX finds no accelerator: {device}")
    check(len(devices) >= chips,
          f"{chips} chips wanted, JAX sees {len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    reading("device", **device, compile_cache=enable_compile_cache(),
            cache_dir_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)
    return device


def _train(grad_sync: str, steps: int) -> None:
    from repro.launch.train import main as train_main
    history = train_main(["--arch", ARCH, "--variant", VARIANT,
                          "--batch", str(BATCH), "--seq", str(SEQ),
                          "--steps", str(steps), "--grad-sync", grad_sync,
                          "--log-every", "1"])
    losses = [h["loss"] for h in history]
    times = [h["step_time_s"] for h in history]
    reading(f"train/{grad_sync}", steps=len(history), losses=losses,
            first_step_s_incl_compile=times[0], later_step_s=times[1:],
            peak_bytes_in_use=peak_bytes())
    check(len(history) == steps, f"{grad_sync}: {len(history)} steps")
    check(all(math.isfinite(x) for x in losses),
          f"{grad_sync}: non-finite loss in {losses}")


def phase_train() -> None:
    _train("auto", AUTO_STEPS)
    _train("canary_fp", FP_STEPS)


def phase_reference() -> None:
    """Chip loss (model dtype) vs CPU float32 loss on batch 0's slice."""
    import jax
    import jax.numpy as jnp
    from repro.data import DataConfig, batch_at
    from repro.models import get_config, init_params
    from repro.train import TrainConfig, make_loss_fn

    cfg = get_config(ARCH, VARIANT)
    batch = batch_at(DataConfig(vocab_size=cfg.vocab_size,
                                global_batch=BATCH, seq_len=SEQ), 0)
    batch = {k: v[:1, :REF_SEQ] for k, v in batch.items()}
    cpu = jax.devices("cpu")[0]
    chip = jax.devices()[0]
    key = jax.random.PRNGKey(SEED)     # the Trainer's initial params
    with jax.default_device(cpu):
        params = jax.jit(partial(init_params, cfg))(key)

    loss_fn = jax.jit(make_loss_fn(TrainConfig(model=cfg)))
    on_chip = jax.device_put((params, batch), chip)
    chip_loss = float(loss_fn(*on_chip)[0])

    cfg32 = cfg.with_(dtype="float32")
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(make_loss_fn(TrainConfig(model=cfg32)))
        cpu_loss = float(ref_fn(*jax.device_put((params32, batch), cpu))[0])
    rel = abs(chip_loss - cpu_loss) / abs(cpu_loss)
    reading("reference", slice=[1, REF_SEQ], chip_loss=chip_loss,
            chip_dtype=cfg.dtype, cpu_float32_loss=cpu_loss, rel_diff=rel,
            rtol=LOSS_RTOL)
    check(math.isfinite(chip_loss) and rel <= LOSS_RTOL,
          f"chip loss {chip_loss} vs CPU float32 {cpu_loss}: rel {rel}")


def _record(seed: int, timeout_ns: float, noise_prob: float):
    """Record one traced CANARY run under background traffic and sender
    noise; returns its compiled schedules and payload bytes."""
    from repro.core.canary import Algo, AllreduceJob, Simulator, scaled_config
    from repro.core.trace import compile_app
    cfg = scaled_config(8, trace=True, seed=seed, timeout_ns=timeout_ns,
                        noise_prob=noise_prob)
    jobs = [AllreduceJob(app=0, participants=list(range(REPLAY_HOSTS)),
                         data_bytes=REPLAY_BYTES)]
    sim = Simulator(cfg, jobs, algo=Algo.CANARY,
                    noise_hosts=list(range(REPLAY_HOSTS, 2 * REPLAY_HOSTS)))
    check(sim.run().correct, f"packet engine run seed={seed} incorrect")
    return compile_app(sim.trace, 0), cfg.payload_bytes


def _tree_shape(schedule) -> tuple:
    return (schedule.depth, tuple(sorted(
        len(step.srcs) for rnd in schedule.reduce_rounds for step in rnd)))


def phase_replay() -> None:
    import jax
    import numpy as np
    from repro.core.trace import fixed_point_replay, reference_allreduce
    from repro.kernels.ops import fixed_point_scale

    t0 = time.perf_counter()
    first, payload = _record(3, 50.0, 0.2)
    second, _ = _record(29, 500.0, 0.05)
    record_s = time.perf_counter() - t0
    check(len(first) == len(second), "traces hold different block counts")
    differ = sum(_tree_shape(a) != _tree_shape(b)
                 for a, b in zip(first, second))

    shape = (REPLAY_HOSTS, len(first), payload // 4)  # float32 payload words
    x = jax.random.normal(jax.random.PRNGKey(SEED), shape)
    runs = []
    for schedules in (first, second):
        t0 = time.perf_counter()
        out, q = fixed_point_replay(schedules, x)
        q = np.asarray(q)
        runs.append((np.asarray(out), q, time.perf_counter() - t0))
    bits = 24                                   # fixed_point_replay default
    scale = float(fixed_point_scale(float(np.max(np.abs(np.asarray(x)))),
                                    bits=bits, world=REPLAY_HOSTS))
    # each of the summands rounds by at most 0.5 / scale
    tol = (REPLAY_HOSTS + 1) * 0.5 / scale
    ref = np.asarray(reference_allreduce(x))
    err = max(float(np.max(np.abs(out - ref))) for out, _, _ in runs)
    identical = bool(np.array_equal(runs[0][1], runs[1][1]))
    reading("replay", inputs=list(shape), blocks_with_different_trees=differ,
            record_s=record_s, replay_s=[r[2] for r in runs],
            q_dtype=str(runs[0][1].dtype), bit_identical=identical,
            max_abs_err=err, tol=tol, peak_bytes_in_use=peak_bytes())
    check(differ > 0, "both traces formed the same trees")
    check(runs[0][1].dtype == np.int32, "replay result is not int32")
    check(identical, "fixed-point replay differs across tree shapes")
    check(err <= tol, f"replay error {err} above {tol}")


def phase_flow() -> None:
    import jax
    from benchmarks.sweep import expand_suite
    from repro.core.flow.batch import run_batch
    from repro.core.flow.model import lower_item, solve_cell

    cells = [lower_item(it)
             for it in expand_suite("fig7", FLOW_TOPOLOGY, FLOW_REPS)]
    t0 = time.perf_counter()
    runtimes, goodputs = run_batch(cells)
    solve_s = time.perf_counter() - t0
    worst = 0.0
    for cell, t, g in zip(cells, runtimes, goodputs):
        t_ref, g_ref = solve_cell(cell)
        worst = max(worst, abs(t - t_ref) / t_ref, abs(g - g_ref) / g_ref)
    reading("flow", topology=FLOW_TOPOLOGY, cells=len(cells),
            backend=jax.default_backend(), solve_s_incl_compile=solve_s,
            max_rel_diff=worst, rtol=FLOW_RTOL)
    check(worst <= FLOW_RTOL, f"flow solve off its host mirror by {worst}")


def _compare(got, ref) -> tuple[float, float]:
    """(relative L2 of the whole gradient, worst leaf's max difference over
    that leaf's largest value) of ``got`` against ``ref``."""
    import numpy as np
    num = sum(float(np.sum((a - r) ** 2)) for a, r in zip(got, ref))
    den = sum(float(np.sum(r ** 2)) for r in ref)
    leaf_max = max(float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
                   for a, r in zip(got, ref) if np.max(np.abs(r)) > 0)
    return math.sqrt(num / den), leaf_max


def phase_grad_sync() -> None:
    """Synced gradients of every explicit mode against auto's, on a
    (dp, 1) mesh, then a few trainer steps under canary."""
    import jax
    import numpy as np
    from repro.core.collective import round_robin_roots
    from repro.data import DataConfig
    from repro.models import get_config
    from repro.parallel.context import ParallelContext, parallel_context
    from repro.train import TrainConfig, Trainer, TrainerConfig, make_grads_fn

    dp = GRAD_DP
    mesh = jax.make_mesh((dp, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    data = DataConfig(vocab_size=get_config(ARCH, VARIANT).vocab_size,
                      global_batch=BATCH, seq_len=SEQ)
    roots = tuple(round_robin_roots(TrainConfig(
        model=get_config(ARCH, VARIANT)).canary_blocks, dp))
    # (dtype, grad_sync, roots, param placement). The Trainer shards params
    # over the data axis for auto (FSDP) and replicates them there for the
    # explicit modes. The last three are a witness in the model's own
    # bfloat16, not checked: auto under both placements tells partitioning
    # apart from grad sync, and canary is set beside auto on the same
    # placement.
    programs = [(GRAD_DTYPE, "auto", None, "fsdp"),
                (GRAD_DTYPE, "canary", None, "replicated"),
                (GRAD_DTYPE, "ring", None, "replicated"),
                (GRAD_DTYPE, "canary_fp", roots, "replicated"),
                (GRAD_DTYPE, "canary_fp", roots[::-1], "replicated"),
                ("bfloat16", "auto", None, "fsdp"),
                ("bfloat16", "auto", None, "replicated"),
                ("bfloat16", "canary", None, "replicated")]
    placed, lowered = {}, []      # (dtype, placement) -> (params, batch)
    with parallel_context(ParallelContext(mesh=mesh, data_axes=("data",),
                                          model_axis="model")), \
            jax.default_matmul_precision("highest"):
        for dtype, mode, plan, where in programs:
            cfg = get_config(ARCH, VARIANT).with_(dtype=dtype)
            if (dtype, where) not in placed:
                tc = TrainConfig(model=cfg, grad_sync="auto" if where == "fsdp"
                                 else "canary")
                trainer = Trainer(TrainerConfig(train=tc, data=data, steps=0),
                                  mesh=mesh, seed=SEED)
                placed[dtype, where] = (trainer.params, trainer.make_batch(0))
                del trainer
            tc = TrainConfig(model=cfg, grad_sync=mode, canary_roots=plan)
            lowered.append(jax.jit(make_grads_fn(tc, mesh)).lower(
                *placed[dtype, where]))
    # the compiles are independent: run them side by side
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as pool:
        compiled = list(pool.map(lambda low: low.compile(), lowered))
    reading("grads/compile", programs=len(compiled),
            wall_s=time.perf_counter() - t0)
    grads, failures = [], []
    for (dtype, mode, plan, where), fn in zip(programs, compiled):
        params, b = placed[dtype, where]
        t0 = time.perf_counter()
        g, metrics = jax.block_until_ready(fn(params, b))
        run_s = time.perf_counter() - t0
        grads.append([np.asarray(x, np.float32) for x in jax.tree.leaves(g)])
        reading(f"grads/{mode}", dtype=dtype, roots=plan, run_s=run_s,
                loss=float(metrics["loss"]),
                param_sharding=str(jax.tree.leaves(params)[0].sharding.spec),
                batch_sharding=str(b["tokens"].sharding.spec),
                peak_bytes_in_use=peak_bytes())
    del placed, compiled

    for i in (1, 2, 3, 4):
        dtype, mode, plan, _ = programs[i]
        rel_l2, leaf_max = _compare(grads[i], grads[0])
        ok = rel_l2 <= GRAD_REL_L2 and leaf_max <= GRAD_LEAF_MAX
        reading(f"grads/{mode}_vs_auto", dtype=dtype, roots=plan,
                rel_l2=rel_l2, leaf_max_rel=leaf_max,
                rel_l2_bound=GRAD_REL_L2, leaf_max_bound=GRAD_LEAF_MAX, ok=ok)
        if not ok:
            failures.append(f"{mode} {plan} gradients off auto's")
    identical = all(np.array_equal(x.view(np.uint32), y.view(np.uint32))
                    for x, y in zip(grads[3], grads[4]))
    reading("grads/canary_fp_root_plans", plans=[roots, roots[::-1]],
            bit_identical=identical)
    if not identical:
        failures.append("canary_fp bits depend on the root plan")
    for name, i, j in (("auto_replicated_vs_auto_fsdp", 6, 5),
                       ("canary_vs_auto_replicated", 7, 6),
                       ("canary_vs_auto_fsdp", 7, 5)):
        rel_l2, leaf_max = _compare(grads[i], grads[j])
        reading(f"grads/witness/{name}", dtype="bfloat16", rel_l2=rel_l2,
                leaf_max_rel=leaf_max)

    from repro.launch.train import main as train_main
    history = train_main(["--arch", ARCH, "--variant", VARIANT,
                          "--batch", str(BATCH), "--seq", str(SEQ),
                          "--steps", str(TRAIN_4CHIP_STEPS),
                          "--grad-sync", "canary",
                          "--data-parallel", str(dp), "--log-every", "1"])
    losses = [h["loss"] for h in history]
    reading("train/canary_dp4", losses=losses,
            step_s=[h["step_time_s"] for h in history],
            peak_bytes_in_use=peak_bytes())
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"non-finite canary loss in {losses}")
    check(not failures, "; ".join(failures))


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    try:
        device = phase_device(args.chips)
    except SmokeFailure as e:
        print(f"smoke FAILED | device | {e}", file=sys.stderr)
        return 1
    if args.chips == 4:
        phases = [("grad_sync", phase_grad_sync)]
    else:
        phases = [("train", phase_train), ("reference", phase_reference),
                  ("replay", phase_replay), ("flow", phase_flow)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:     # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
        reading(f"{name}/done", seconds=time.perf_counter() - t0,
                ok=name not in failed)
    if failed:
        print(f"smoke FAILED | {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
