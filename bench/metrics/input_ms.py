"""Mean host time of one ``Trainer.make_batch`` call inside the timed
window, in ms: the program's ``make_batch`` spans that lie inside the
benchmark's ``bench_window`` span."""


def read(run):
    if run.trace is None:
        return None
    host = run.trace.host
    window = [e for e in host if e.name == "bench_window"]
    spans = [e.end - e.start for e in host if e.name == "make_batch" and any(
        w.start <= e.start and e.end <= w.end for w in window)]
    return sum(spans) / len(spans) / 1e6 if spans else None
