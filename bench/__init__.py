"""The on-chip benchmark: the harness, traffic drivers, plain references
and the reduction from traces to metrics (see ``run.py``)."""
