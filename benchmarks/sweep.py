"""Parallel sweep runner: fan simulation configs/seeds across CPU cores.

Figure suites are embarrassingly parallel — every (algorithm, congestion,
seed) cell is an independent ``Simulator`` run — but the per-figure scripts
run them serially, which is what makes the paper-scale (1024-host) sweeps
intractable on one core. This runner expands a named sweep into a work list,
executes it on a ``multiprocessing`` pool, and writes machine-readable JSON
(per-cell results + per-label aggregates + wall-clock/speedup accounting).

``--backend`` selects the executor (``repro.core.canary.BACKENDS``):

* ``packet`` (default) — the exact discrete-event engine, one worker
  process per cell.
* ``flow`` — the flow-level model (``repro.core.flow``): the whole matrix
  is lowered and solved as one batched JAX call in-process; ``--procs`` is
  ignored. With ``--speedup-probe N`` (default on) the first N cells are
  also run through the packet engine for a like-for-like wall-clock
  comparison, recorded under ``speedup_probe`` in the JSON.

Usage::

    PYTHONPATH=src python -m benchmarks.sweep --suite fig7 --procs 8 \
        --out sweep_fig7.json
    PYTHONPATH=src python -m benchmarks.sweep --suite fig7 --procs 0   # serial
    PYTHONPATH=src python -m benchmarks.sweep --suite fig7 \
        --topology fat_tree_1024 --backend flow   # paper scale, seconds

Suites honour the same env knobs as the rest of the benchmark suite
(``BENCH_FAST=1``, ``BENCH_PAPER_SCALE=1``). ``--topology three_tier`` runs
the same sweep on the 3-tier folded Clos; any ``PAPER_SCALES`` name
(``fat_tree_1024`` ... ``three_tier_4096``) selects a paper-scale fabric.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import multiprocessing as mp
import os
import statistics
import sys
import time
from typing import Dict, List


def _default_procs() -> int:
    return max(1, (os.cpu_count() or 2) - 1)


# --------------------------------------------------------------------------
# Work items (must be picklable: plain dicts in, plain dicts out)
# --------------------------------------------------------------------------
def _base_cfg(topology: str):
    from repro.core.canary import (PAPER_SCALES, paper_scale_config,
                                   three_tier_config)

    from .common import bench_cfg
    if topology in PAPER_SCALES:
        return paper_scale_config(topology)
    if topology == "three_tier":
        return three_tier_config(num_pods=4, leaves_per_pod=2,
                                 hosts_per_leaf=8, aggs_per_pod=2, num_cores=4)
    if topology != "fat_tree":
        raise SystemExit(f"unknown topology {topology!r} (have: fat_tree, "
                         f"three_tier, {', '.join(sorted(PAPER_SCALES))})")
    return bench_cfg()


def expand_suite(suite: str, topology: str, reps: int) -> List[dict]:
    """Expand a named sweep into independent work-item dicts."""
    from .common import bench_size
    cfg = _base_cfg(topology)
    n = max(2, int(cfg.num_hosts * 0.5))  # 50% participants, like bench_hosts
    size = bench_size()
    items: List[dict] = []
    if suite == "fig7":
        # static 1/2/4/8 trees vs canary, with and without congestion
        cells = [("static1", "static_tree", 1), ("static2", "static_tree", 2),
                 ("static4", "static_tree", 4), ("static8", "static_tree", 8),
                 ("canary", "canary", 1)]
        for cong in (False, True):
            for label, algo, nt in cells:
                for rep in range(reps):
                    items.append(dict(label=f"{label}/cong={int(cong)}",
                                      algo=algo, n_trees=nt, congestion=cong,
                                      num_hosts=n, data_bytes=size, rep=rep))
    elif suite == "fig8":
        # goodput vs fraction of hosts running the allreduce, the rest
        # generating congestion (same axis as benchmarks/fig8_*.py)
        for frac in (0.05, 0.25, 0.5, 0.75):
            nf = max(2, int(cfg.num_hosts * frac))
            for algo in ("static_tree", "canary"):
                for rep in range(reps):
                    items.append(dict(label=f"{algo}/hosts={int(frac * 100)}%",
                                      algo=algo, n_trees=1, congestion=True,
                                      num_hosts=nf, data_bytes=size, rep=rep))
    elif suite == "lb":
        # load-balancing policy sensitivity under congestion
        for lb in ("ecmp", "adaptive", "per_packet"):
            for rep in range(reps):
                items.append(dict(label=f"canary/lb={lb}", algo="canary",
                                  n_trees=1, congestion=True, lb=lb,
                                  num_hosts=n, data_bytes=size, rep=rep))
    else:
        raise SystemExit(f"unknown sweep suite {suite!r} (have: fig7, fig8, lb)")
    for it in items:
        it["topology"] = topology
        it["cfg"] = dataclasses.asdict(cfg)
    return items


def run_item(item: dict) -> dict:
    """Execute one packet-engine sweep cell (runs in a worker process)."""
    from repro.core.canary.backends import PacketBackend
    return PacketBackend().run_cell(item)


def _progress(done: int, total: int, t0: float) -> None:
    rate = done / max(1e-9, time.perf_counter() - t0)
    eta = (total - done) / rate if rate > 0 else float("inf")
    print(f"\r# sweep {done}/{total} cells "
          f"({rate:.2f} cells/s, eta {eta:.0f}s)",
          end="" if done < total else "\n", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------
def _run_items_packet(items: List[dict], procs: int) -> List[dict]:
    """Packet-engine execution: worker pool (or in-process when procs<=1).

    ``imap_unordered`` keeps every worker busy and lets us emit progress as
    cells land; results are re-keyed back to submission order afterwards, so
    the result set is identical to a serial run (the equality contract in
    tests/benchmarks/test_sweep.py).
    """
    t0 = time.perf_counter()
    if procs and procs > 1:
        indexed = list(enumerate(items))
        # spawn, never fork: the parent may hold an accelerator through JAX,
        # and a forked child would inherit that state
        ctx = mp.get_context("spawn")
        cells: List[dict] = [None] * len(items)  # type: ignore[list-item]
        with ctx.Pool(processes=procs) as pool:
            done = 0
            for idx, cell in pool.imap_unordered(_run_indexed, indexed,
                                                 chunksize=1):
                cells[idx] = cell
                done += 1
                _progress(done, len(items), t0)
        return cells
    out = []
    for i, it in enumerate(items):
        out.append(run_item(it))
        _progress(i + 1, len(items), t0)
    return out


def _run_indexed(pair):
    idx, item = pair
    return idx, run_item(item)


def _speedup_probe(items: List[dict], flow_cells: List[dict],
                   probe_n: int) -> dict:
    """Like-for-like flow vs packet wall-clock on the first ``probe_n``
    cells of this very grid, plus an extrapolation of what the packet
    engine would cost for the full matrix (per-cell packet cost scales with
    simulated time x hosts; we scale by measured probe cost)."""
    probe = items[:probe_n]
    t0 = time.perf_counter()
    packet_cells = [run_item(it) for it in probe]
    packet_wall = time.perf_counter() - t0
    flow_wall = sum(c["wall_s"] for c in flow_cells)
    # packet cost of the unprobed cells, extrapolated from the probed ones
    # via predicted runtimes (events ~ simulated ns at fixed topology)
    probe_pred = sum(c["runtime_us"] for c in flow_cells[:probe_n])
    total_pred = sum(c["runtime_us"] for c in flow_cells)
    scale = total_pred / probe_pred if probe_pred > 0 else float("nan")
    packet_extrapolated = packet_wall * scale
    return dict(
        probe_cells=probe_n,
        packet_wall_s=packet_wall,
        packet_events=sum(c["events"] for c in packet_cells),
        flow_wall_s=flow_wall,
        packet_extrapolated_s=packet_extrapolated,
        speedup_probe_only=packet_wall / max(1e-9, sum(
            c["wall_s"] for c in flow_cells[:probe_n])),
        speedup_full_matrix=packet_extrapolated / max(1e-9, flow_wall),
    )


def provenance() -> dict:
    from .common import provenance as _prov
    return _prov()


def trace_first_cell(items: List[dict], path: str) -> dict:
    """Re-run the sweep's first cell in-process with the telemetry hub live
    and dump the Perfetto trace to ``path`` (the pool workers' results cross
    a pickle boundary, so the hub object itself never leaves them)."""
    from repro.core.canary import Algo
    from repro.core.canary.algorithms import build_cell_simulator
    from repro.core.canary.backends import item_config
    from repro.core.telemetry import validate_perfetto, write_perfetto
    it = items[0]
    cfg = dataclasses.replace(item_config(it), telemetry=True)
    sim = build_cell_simulator(cfg, Algo(it["algo"]), it["num_hosts"],
                               it["data_bytes"], n_trees=it["n_trees"],
                               congestion=it["congestion"], rep=it["rep"])
    sim.run()
    doc = write_perfetto(sim.telemetry, path)
    errs = validate_perfetto(doc)
    if errs:
        raise SystemExit(f"invalid trace for cell {it['label']!r}: {errs[:3]}")
    print(f"# traced cell {it['label']!r} -> {path} "
          f"({len(doc['traceEvents'])} events)", file=sys.stderr, flush=True)
    return doc


def run_sweep(suite: str, topology: str = "fat_tree", reps: int = 2,
              procs: int = 0, backend: str = "packet",
              speedup_probe: int = 0, telemetry: bool = False) -> dict:
    """Run a sweep; ``procs=0`` means serial (in-process), ``procs>=1`` uses a
    worker pool (packet backend only — the flow backend batches in-process).
    Returns the JSON-ready result document."""
    items = expand_suite(suite, topology, reps)
    if telemetry:
        if backend != "packet":
            raise SystemExit("--telemetry needs the packet backend "
                             "(the flow model has nothing to observe)")
        for it in items:
            it["cfg"]["telemetry"] = True
    t0 = time.perf_counter()
    if backend == "packet":
        cells = _run_items_packet(items, procs)
        extra = {}
    else:
        from repro.core.canary import get_backend
        bk = get_backend(backend)
        cells = bk.run_cells(items)
        extra = {"jit_traces": cells[0].get("jit_traces") if cells else 0}
        if speedup_probe > 0:
            extra["speedup_probe"] = _speedup_probe(
                items, cells, min(speedup_probe, len(items)))
    wall = time.perf_counter() - t0
    by_label: Dict[str, List[dict]] = {}
    for c in sorted(cells, key=lambda c: (c["label"], c["rep"])):
        by_label.setdefault(c["label"], []).append(c)
    aggregates = {
        label: dict(
            goodput_gbps_mean=statistics.mean(c["goodput_gbps"] for c in cs),
            runtime_us_mean=statistics.mean(c["runtime_us"] for c in cs),
            correct=all(c["correct"] for c in cs),
            reps=len(cs),
        )
        for label, cs in sorted(by_label.items())
    }
    cpu_s = sum(c["wall_s"] for c in cells)
    return dict(
        suite=suite, topology=topology, reps=reps, procs=procs,
        backend=backend,
        cells=len(cells), wall_s=wall, cpu_s=cpu_s,
        speedup=(cpu_s / wall) if wall > 0 else 0.0,
        correct=all(c["correct"] for c in cells),
        provenance=provenance(),
        aggregates=aggregates,
        results=cells,
        items=items,
        **extra,
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", default="fig7", help="fig7 | fig8 | lb")
    ap.add_argument("--topology", default="fat_tree",
                    help="fat_tree | three_tier | a PAPER_SCALES name "
                         "(fat_tree_1024 ... three_tier_4096)")
    ap.add_argument("--backend", default="packet",
                    help="packet (exact, default) | flow (batched model)")
    ap.add_argument("--reps", type=int,
                    default=int(os.environ.get("SWEEP_REPS", "2")))
    ap.add_argument("--procs", type=int, default=_default_procs(),
                    help="worker processes (0/1 = serial; packet only)")
    ap.add_argument("--speedup-probe", type=int, default=4,
                    help="flow backend: run N cells through the packet "
                         "engine too and record the wall-clock comparison "
                         "(0 disables)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the telemetry hub in every cell (packet "
                         "backend only); per-cell summaries land in the "
                         "result JSON under 'telemetry'")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="re-run the first cell in-process with telemetry "
                         "and write its Perfetto trace-event JSON here")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args(argv)
    doc = run_sweep(args.suite, args.topology, args.reps, args.procs,
                    backend=args.backend,
                    speedup_probe=args.speedup_probe
                    if args.backend != "packet" else 0,
                    telemetry=args.telemetry)
    if args.trace_out:
        trace_first_cell(doc["items"], args.trace_out)
    suffix = "" if args.backend == "packet" else f"_{args.backend}"
    out = args.out or f"sweep_{args.suite}_{args.topology}{suffix}.json"
    with open(out, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"# {doc['cells']} cells in {doc['wall_s']:.1f}s wall "
          f"({doc['cpu_s']:.1f}s cpu, {doc['speedup']:.1f}x speedup, "
          f"backend={args.backend}, procs={args.procs}) "
          f"correct={doc['correct']} -> {out}",
          file=sys.stderr)
    if "speedup_probe" in doc:
        sp = doc["speedup_probe"]
        print(f"# flow vs packet: {sp['speedup_probe_only']:.0f}x on "
              f"{sp['probe_cells']} probed cells, "
              f"{sp['speedup_full_matrix']:.0f}x extrapolated full-matrix "
              f"({sp['packet_extrapolated_s']:.0f}s packet vs "
              f"{sp['flow_wall_s']:.2f}s flow)", file=sys.stderr)
    from .common import emit
    for label, agg in doc["aggregates"].items():
        # emit() also records the row for run.py's BENCH_RESULTS.json
        emit(f"sweep/{args.suite}/{label}", agg["runtime_us_mean"],
             f"goodput_gbps={agg['goodput_gbps_mean']:.1f};"
             f"correct={agg['correct']}")


if __name__ == "__main__":
    main()
