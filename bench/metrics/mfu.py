"""Model FLOPs of a step (``bench.flops``, no recomputation) over the
chips' peak bf16 rate times the step period the device trace shows: the
mean time from the start of one step program to the start of the next."""
from bench import trace


def read(run):
    periods = [p for p in (trace.step_period_ns(w)
                           for w in run.windows.values()) if p]
    if not periods:
        return None
    period_s = sum(periods) / len(periods) / 1e9
    return 100.0 * run.flops_per_step / (
        run.chips * run.peak["bf16_flops_per_s"] * period_s)
