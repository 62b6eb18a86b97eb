#!/usr/bin/env python3
"""Run one cell of the device benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Cells, configurations, traffic and metrics
are named in ``BENCHMARK.json``. With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics from a
profiler trace of the window. JAX's compilation cache is kept in
``bench/.jax_cache`` of the checkout, so only a checkout's first run of a
cell compiles.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# before JAX is imported: it reads these once
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
# the TPU runtime keeps its logs in the checkout, not in its default place
os.environ["TPU_LOG_DIR"] = os.path.join(BENCH, ".tpu_logs")

if __name__ == "__main__":
    try:
        from bench import harness
    except ImportError as e:          # a checkout without the program
        print(f"no result: {e}", file=sys.stderr)
        sys.exit(3)
    code = harness.main(sys.argv[1:], t_start=T_START)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
