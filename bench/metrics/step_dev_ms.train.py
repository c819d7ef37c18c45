"""Device time of one train step (the jitted ``train_step``)."""
from bench.measure import step_dev_ms


def read(run):
    return step_dev_ms(run, "train_step")
