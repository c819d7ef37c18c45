"""Device time of one decode step (the jitted ``serve_step``)."""
from bench.measure import step_dev_ms


def read(run):
    return step_dev_ms(run, "serve_step")
