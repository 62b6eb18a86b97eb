"""Share of the traced window in which no operation runs on the device,
averaged over the cell's chips. The window of a chip runs from the start of
its first step program to the end of its last."""


def read(run):
    ws = list(run.windows.values())
    if not ws:
        return None
    return 100.0 * sum(1 - w.busy_ns / w.window_ns for w in ws) / len(ws)
