"""Device time of one prefill step (the jitted ``prefill_step``)."""
from bench.measure import step_dev_ms


def read(run):
    return step_dev_ms(run, "prefill_step")
