#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out FILE]

For each seed: the cell's set-up and compared steps, as a run takes them
(no window), then the float32 reference, and the three numbers. For each
control seed besides: the control, which is the reference with every
product in float8 (e4m3) put in the program's place, and the faults that a
training cell can have, planted in the reference: half of the batch left
out (``half``) and, on more than one chip, the exchange between chips left
out (``local``). A step that returns its state unchanged reads 1 in
``change_gap`` by construction and is not run. One JSON line per reading.
The benchmark's own runs do not run this.
"""
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
# the TPU runtime keeps its logs in the checkout, not in its default place
os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, ".tpu_logs")


def main(argv=None) -> int:
    import argparse
    import gc
    import json
    import time

    from bench import cell as training, check, harness, spec
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}

    cell = spec.cell(args.workload)
    devices = harness.check_device(cell.chips, spec.peaks())
    enable_compile_cache()
    wl = cell.workload
    refs = {}

    def reference(precision):
        if precision not in refs:
            refs[precision] = check.Reference(
                cell.model, wl, cell.config["reference"], precision)
        return refs[precision]

    out = open(args.out, "a") if args.out else None

    def emit(**row):
        line = json.dumps(row, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in sorted(set(seeds) | controls):
        t0 = time.perf_counter()
        prep = training.prepare(cell, seed, devices)
        prog = prep.readings
        training.free(prep)
        gc.collect()
        t1 = time.perf_counter()
        ref = reference("float32").run(seed)
        t2 = time.perf_counter()
        emit(cell=cell.name, seed=seed, kind="program", setup_s=t1 - t0,
             reference_s=t2 - t1, **check.gaps(prog, ref),
             losses=prog.losses, ref_losses=ref.losses,
             grad={k: (prog.grad[k], ref.grad[k]) for k in ref.grad},
             change={k: (prog.change[k], ref.change[k]) for k in ref.change})
        if seed not in controls:
            continue
        ctl = reference("float8_e4m3fn").run(seed)
        emit(cell=cell.name, seed=seed, kind="control",
             **check.gaps(ctl, ref), losses=ctl.losses)
        faults = ["half"] + (["local"] if wl.get("data_parallel", 1) > 1
                             else [])
        for fault in faults:
            bad = reference("float32").run(seed, fault)
            emit(cell=cell.name, seed=seed, kind=fault,
                 **check.gaps(bad, ref), losses=bad.losses)
    return 0


if __name__ == "__main__":
    sys.exit(main())
