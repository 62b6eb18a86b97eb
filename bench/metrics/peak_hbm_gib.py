"""Peak device memory after the window, on the fullest of the cell's chips,
in GiB: ``peak_bytes_in_use`` plus ``peak_bytes_reserved``, the scratch
the runtime holds for the compiled programs (``harness.memory_peak``)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2 ** 30
