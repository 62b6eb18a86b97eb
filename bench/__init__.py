"""Device benchmark of the training stack: one cell per run (``run.py``)."""
