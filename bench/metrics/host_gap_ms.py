"""Mean device-idle time between consecutive step programs, in ms: the
runtime's own turn from one program to the next, and wherever the host
fell behind the steps dispatched ahead (the next batch, the dispatch, a
stall)."""
from bench import trace


def read(run):
    gaps = [g for w in run.windows.values() for g in trace.gap_idle_ns(w)]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
