import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) pair this lowers + compiles the real
train/serve step on the production meshes — 16x16 single-pod and 2x16x16
multi-pod — using ShapeDtypeStruct stand-ins (no allocation), then extracts:

* ``compiled.memory_analysis()``  — per-device bytes (proves it fits),
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes accessed,
* collective bytes parsed from the optimized HLO (all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute result sizes),

and derives the three §Roofline terms. Results land in
``experiments/dryrun/<arch>__<shape>__<mesh>[__<gradsync>].json``.

NOTE: the XLA_FLAGS line above must execute before any other jax import in
the process; run this module as the entry point
(``python -m repro.launch.dryrun``), never import it from a process that
already initialized jax with a different device count.
"""
import argparse
import json
import re
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16, mesh_axes,
                               make_production_mesh)
from repro.models import get_config, init_cache, init_params, list_archs
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.optim import init as adamw_init
from repro.parallel.context import ParallelContext, parallel_context
from repro.parallel.sharding import batch_spec, cache_specs, param_specs
from repro.serving import make_serve_step
from repro.train import TrainConfig, make_train_step

from repro.launch.analysis import (INPUT_SHAPES, _COLLECTIVES,
                                   _DTYPE_BYTES,
                                   model_flops_per_step,
                                   parse_collective_bytes)


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(shapes_tree, specs_tree, mesh):
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes_tree,
        specs_tree)


def build_dryrun(arch: str, shape_name: str, mesh, grad_sync: str = "auto",
                 cfg_override: Optional[ModelConfig] = None,
                 microbatches: int = 1, moe_impl: str = ""
                 ) -> Tuple[Any, Tuple, ModelConfig]:
    """Returns (fn, example_args_sds, cfg) ready for jit().lower()."""
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    seq, gb = spec["seq_len"], spec["global_batch"]
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    if moe_impl:
        cfg = cfg.with_(moe_impl=moe_impl)
    dp_axes, model_axis = mesh_axes(mesh)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    if kind == "decode" and shape_name == "long_500k":
        if not cfg.supports_long_decode():
            raise ValueError(f"{arch} skips long_500k (see DESIGN.md §5)")
        cfg = cfg.long_context_variant(window=8192)

    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(partial(init_params, cfg), key)
    # explicit grad sync reduces over data axes itself -> params replicated
    use_fsdp = grad_sync == "auto"
    p_specs = param_specs(params_shapes, mesh, fsdp=dp, model=model_axis,
                          use_fsdp=use_fsdp)
    params_sds = _tree_sds(params_shapes, p_specs, mesh)

    if kind == "train":
        oc = AdamWConfig(state_dtype="bfloat16"
                         if cfg.param_count() > 1e11 else "float32")
        tc = TrainConfig(model=cfg, optimizer=oc, grad_sync=grad_sync,
                         microbatches=microbatches)
        step = make_train_step(tc, mesh=mesh, dp_axes=dp_axes)
        opt_shapes = jax.eval_shape(lambda p: adamw_init(p, oc),
                                    params_shapes)
        from repro.optim import AdamWState
        opt_sds = AdamWState(
            step=_sds((), jnp.int32, mesh, P()),
            m=_tree_sds(opt_shapes.m, p_specs, mesh),
            v=_tree_sds(opt_shapes.v, p_specs, mesh))
        bspec = batch_spec(mesh, gb, dp)
        text_seq = seq - (cfg.num_patches if cfg.frontend == "vision_stub"
                          else 0)
        batch = {
            "tokens": _sds((gb, text_seq), jnp.int32, mesh, bspec),
            "labels": _sds((gb, text_seq), jnp.int32, mesh, bspec),
        }
        dt = jnp.dtype(cfg.dtype)
        if cfg.frontend == "audio_stub":
            batch["frames"] = _sds((gb, cfg.encoder_seq, cfg.d_model), dt,
                                   mesh, bspec)
        if cfg.frontend == "vision_stub":
            batch["patches"] = _sds((gb, cfg.num_patches, cfg.d_model), dt,
                                    mesh, bspec)
        return step, (params_sds, opt_sds, batch), cfg

    if kind == "prefill":
        from repro.models import forward

        def prefill_fn(params, batch):
            kw = {}
            if "frames" in batch:
                kw["frames"] = batch["frames"]
            if "patches" in batch:
                kw["extra_embeds"] = batch["patches"]
            logits, _ = forward(params, batch["tokens"], cfg, **kw)
            return jax.lax.with_sharding_constraint(
                logits, NamedSharding(mesh, P(dp, None, model_axis)))

        bspec = batch_spec(mesh, gb, dp)
        text_seq = seq - (cfg.num_patches if cfg.frontend == "vision_stub"
                          else 0)
        batch = {"tokens": _sds((gb, text_seq), jnp.int32, mesh, bspec)}
        dt = jnp.dtype(cfg.dtype)
        if cfg.frontend == "audio_stub":
            batch["frames"] = _sds((gb, cfg.encoder_seq, cfg.d_model), dt,
                                   mesh, bspec)
        if cfg.frontend == "vision_stub":
            batch["patches"] = _sds((gb, cfg.num_patches, cfg.d_model), dt,
                                    mesh, bspec)
        return prefill_fn, (params_sds, batch), cfg

    # decode
    serve = make_serve_step(cfg)
    cache_shapes = jax.eval_shape(partial(init_cache, cfg, gb, seq), )
    c_specs = cache_specs(cache_shapes, mesh, dp_axes=dp, model=model_axis)
    cache_sds = _tree_sds(cache_shapes, c_specs, mesh)
    bspec = batch_spec(mesh, gb, dp)
    tokens = _sds((gb, 1), jnp.int32, mesh, bspec)
    return serve, (params_sds, cache_sds, tokens), cfg


def _probe_costs(arch: str, shape_name: str, mesh, grad_sync: str,
                 n_periods: int, microbatches: int = 1,
                 moe_impl: str = "") -> Dict[str, float]:
    """Lower an UNROLLED shallow clone (n_periods repeat periods) and return
    its per-device costs. XLA's HloCostAnalysis counts a ``while`` body once
    regardless of trip count, so scanned-stack costs must be extrapolated
    from two unrolled probes (see extrapolated_costs)."""
    import repro.models.registry as registry
    from repro.models.transformer import layer_period
    cfg_full = get_config(arch)
    per = layer_period(cfg_full)
    overrides = dict(num_layers=per * n_periods, scan_layers=False,
                     remat=False)
    if cfg_full.is_encoder_decoder:
        overrides["encoder_layers"] = n_periods
    probe_cfg = cfg_full.with_(**overrides)
    orig_get = registry.get_config
    try:
        registry.get_config = lambda n, v="full": probe_cfg \
            if n == arch else orig_get(n, v)
        # rebuild through the same path so shardings/steps are identical
        fn, args, _ = build_dryrun(arch, shape_name, mesh,
                                   grad_sync=grad_sync, cfg_override=probe_cfg,
                                   microbatches=microbatches,
                                   moe_impl=moe_impl)
    finally:
        registry.get_config = orig_get
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "link_bytes": coll["total_link_bytes"],
    }


def extrapolated_costs(arch: str, shape_name: str, mesh, grad_sync: str,
                       n_periods_full: int, microbatches: int = 1,
                       moe_impl: str = "") -> Dict[str, float]:
    """cost(L periods) = fixed + L * per_period  =>  probe at 1 and 2."""
    c1 = _probe_costs(arch, shape_name, mesh, grad_sync, 1, microbatches,
                      moe_impl)
    c2 = _probe_costs(arch, shape_name, mesh, grad_sync, 2, microbatches,
                      moe_impl)
    out = {}
    for k in c1:
        delta = max(0.0, c2[k] - c1[k])
        fixed = max(0.0, c1[k] - delta)
        out[k] = fixed + n_periods_full * delta
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            grad_sync: str = "auto", out_dir: str = "experiments/dryrun",
            save_hlo: bool = False, seq_parallel: bool = False,
            microbatches: int = 1, tag: str = "",
            moe_impl: str = "") -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes, model_axis = mesh_axes(mesh)
    ctx = ParallelContext(mesh=mesh, data_axes=dp_axes, model_axis=model_axis,
                          sequence_parallel=seq_parallel)
    t0 = time.time()
    with parallel_context(ctx):
        fn, args, cfg = build_dryrun(arch, shape_name, mesh,
                                     grad_sync=grad_sync,
                                     microbatches=microbatches,
                                     moe_impl=moe_impl)
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    chips = mesh.devices.size
    spec = INPUT_SHAPES[shape_name]
    from repro.models.transformer import layer_period
    n_per = cfg.num_layers // layer_period(cfg)
    with parallel_context(ctx):
        extr = extrapolated_costs(arch, shape_name, mesh, grad_sync, n_per,
                                  microbatches, moe_impl)
    # the microbatch accumulation loop is also a scan whose body XLA counts
    # once; each iteration does ~1/k of the step's work
    mb_scale = microbatches if spec["kind"] == "train" else 1
    flops_dev = extr["flops"] * mb_scale
    bytes_dev = extr["bytes"] * mb_scale
    coll_bytes_extr = extr["link_bytes"] * mb_scale
    mf = model_flops_per_step(cfg, spec["kind"], spec["seq_len"],
                              spec["global_batch"])
    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_bytes_extr / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(chips), "grad_sync": grad_sync,
        "seq_parallel": seq_parallel, "microbatches": microbatches,
        "compile_s": round(t_compile, 1),
        "model_variant": cfg.name,
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_bytes": bytes_dev,
            "collective_link_bytes": coll_bytes_extr,
            "collectives_scanned_body": coll["per_op_bytes"],
            "collective_counts_scanned_body": coll["per_op_count"],
            "raw_scanned_flops": float(ca.get("flops", 0.0)),
        },
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "total_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        "roofline": {
            **{k: v for k, v in terms.items()},
            "dominant": dominant,
            "model_flops_global": mf,
            "model_flops_per_device": mf / chips,
            "useful_flops_ratio": (mf / chips) / flops_dev
            if flops_dev else 0.0,
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{grad_sync}" if grad_sync != "auto" else ""
    if tag:
        suffix += f"__{tag}"
    fname = f"{arch.replace('/', '_')}__{shape_name}__" \
            f"{result['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(result, f, indent=1)
    if save_hlo:
        with open(os.path.join(out_dir, fname.replace(".json", ".hlo")),
                  "w") as f:
            f.write(hlo)
    return result


def should_skip(arch: str, shape_name: str) -> Optional[str]:
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.supports_long_decode():
        return "enc-dec full attention — documented skip (DESIGN.md §5)"
    return None


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all"] + list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--grad-sync", default="auto")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--moe-impl", default="")
    args = ap.parse_args()
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        for shape in shapes:
            skip = should_skip(arch, shape)
            if skip:
                print(f"SKIP  {arch:18s} {shape:12s}: {skip}", flush=True)
                continue
            for mp in meshes:
                tag = f"{arch:18s} {shape:12s} {'2x16x16' if mp else '16x16 '}"
                try:
                    r = run_one(arch, shape, mp, grad_sync=args.grad_sync,
                                out_dir=args.out, save_hlo=args.save_hlo,
                                seq_parallel=args.seq_parallel,
                                microbatches=args.microbatches, tag=args.tag,
                                moe_impl=args.moe_impl)
                    roof = r["roofline"]
                    print(f"OK    {tag} compile={r['compile_s']:6.1f}s "
                          f"mem/dev={r['memory']['total_bytes']/2**30:6.2f}GiB "
                          f"dom={roof['dominant']:12s} "
                          f"useful={roof['useful_flops_ratio']:.2f}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)))
                    print(f"FAIL  {tag}: {e}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nall dry-runs compiled.")


if __name__ == "__main__":
    main()
