"""One run of one cell: device check, set-up, window, metrics, the check.

The result is the last line of standard output, one JSON object; the
numbers that decided ``correct`` are the last lines of standard error and
the last key of that object. Nothing is printed as a result where JAX finds
no TPU of a kind in the peak table, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from bench import spec

STEP_PROGRAM = r"train_step"     # name of the Trainer's jitted step


class NoDevice(RuntimeError):
    pass


def check_device(chips: int, peaks: Dict) -> List:
    """The devices of the run: JAX's TPUs, of a kind in the peak table, at
    least ``chips`` of them. Anything else raises ``NoDevice``."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoDevice(f"JAX finds no TPU, only {d.platform}")
    if d.device_kind not in peaks:
        raise NoDevice(f"device_kind {d.device_kind!r} is not in the peak "
                       f"table ({sorted(peaks)})")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices


@dataclass
class RunRecord:
    """What a metric reader reads."""
    cell: spec.Cell
    seed: int
    chips: int
    peak: Dict                       # the peak table's row for this device
    flops_per_step: float
    window: object = None            # cell.Window
    setup_s: float = 0.0
    memory_peak_bytes: Optional[int] = None
    trace: object = None             # trace.Trace
    windows: Dict = field(default_factory=dict)   # device -> DeviceWindow
    step_hlo: Optional[Callable[[], str]] = None  # the compiled step's HLO


def memory_peak(devices) -> Optional[int]:
    """Peak device memory of the fullest chip: the peak of the buffers in
    use plus the peak that the runtime reserved for its programs' scratch.
    On a TPU v5 lite ``peak_bytes_in_use`` alone leaves the compiled step's
    temporaries out (PERF.md, Findings)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def _metrics(entries, record) -> Dict:
    out = {}
    for m in entries:
        value = spec.reader(m["name"]).read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _process_age_s() -> float:
    """Seconds since this process started, from /proc where it exists."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    age0 = _process_age_s() - (time.perf_counter() - t_start)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    peaks = spec.peaks()
    try:
        devices = check_device(cell.chips, peaks)
    except NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return run(cell, args, devices, peaks, t_start, age0)


def run(cell, args, devices, peaks, t_start: float, age0: float) -> int:
    from bench import cell as training, check, flops, trace
    used = devices[:cell.chips]
    wl = cell.workload
    record = RunRecord(
        cell=cell, seed=args.seed, chips=cell.chips,
        peak=peaks[devices[0].device_kind],
        flops_per_step=flops.train_flops_per_step(
            cell.config, wl["global_batch"], wl["seq_len"]))
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(spec.BENCH, "traces", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)

    prep = training.prepare(cell, args.seed, devices)
    record.window = training.window(prep, cell, args.seed, args.seconds,
                                    trace_dir)
    record.setup_s = age0 + record.window.start - t_start
    record.memory_peak_bytes = memory_peak(used)
    print(f"memory_stats of chip 0: {used[0].memory_stats()}",
          file=sys.stderr)
    if args.trace:
        record.trace = trace.load(trace_dir)
        record.windows = trace.device_windows(record.trace, STEP_PROGRAM)
        record.step_hlo = lambda: training.step_hlo(prep.trainer)
        metrics = _metrics(cell.per_layer, record)
    else:
        metrics = _metrics(cell.end_to_end, record)
    prog = prep.readings
    training.free(prep)
    gc.collect()

    t_ref = time.perf_counter()
    ref = check.Reference(cell.model, wl, cell.config["reference"])
    values = check.gaps(prog, ref.run(args.seed))
    w = record.window
    print(f"reference took {time.perf_counter() - t_ref:.1f} s; window "
          f"{w.steps} steps in {w.seconds:.3f} s, {w.ahead} in flight at "
          f"most, dispatches apart by {max(w.dispatch_s):.3f} s at most, "
          f"median {statistics.median(w.dispatch_s):.3f} s", file=sys.stderr)
    limits = {k: cell.limits[k]["limit"] for k in check.NUMBERS}
    compared = [k for k in check.NUMBERS if limits[k] is not None]
    correct = check.verdict(values, limits)

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record.memory_peak_bytes}
    result = {"correct": correct, "attempted": record.window.steps,
              "failed": record.window.nonfinite, "metrics": metrics,
              "device": device}
    if args.trace and record.windows:
        ws = list(record.windows.values())
        device["busy_s"] = sum(w.busy_ns for w in ws) / len(ws) / 1e9
        device["window_s"] = sum(w.window_ns for w in ws) / len(ws) / 1e9
        first = ws[0]
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace.top_ops(record.windows)],
            "idle_gaps": [list(x) for x in trace.idle_gaps(
                record.trace, first, skip=("bench_window",))]}
    result["check"] = {k: {"value": values[k], "limit": limits[k]}
                       for k in compared}
    sys.stdout.flush()
    for k in check.NUMBERS:
        if k not in compared:
            print(f"reading {k} {values[k]!r}, not compared", file=sys.stderr)
    for k in compared:
        print(f"check {k} {values[k]!r} limit {limits[k]!r} "
              f"(leaf {values.get(k + '_leaf', '-')})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    return 0
