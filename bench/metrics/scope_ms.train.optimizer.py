"""Device milliseconds per step of the optimizer's ops in a train step (the
jitted train_step): the learning-rate schedule, global-norm clipping and
AdamW; every op under the program's optimizer scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "train_step", "optimizer", "scope_ms.train.optimizer")
