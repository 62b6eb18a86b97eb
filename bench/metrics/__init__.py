"""One reader per metric: ``read(run)`` returns the metric's value from a
``bench.harness.RunRecord``, or None where the run has nothing to read."""
