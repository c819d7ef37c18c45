"""Device milliseconds per step of the backward's ops in a train step (the
jitted train_step): every op whose scope path JAX marks as transposed,
recompute under remat included."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "train_step", "backward", "scope_ms.train.backward")
