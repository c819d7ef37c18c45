"""A train step's model FLOPs (forward and backward, recompute not
counted) over the device time of one train step (the jitted
``train_step``, traced window) and the chip's peak FLOP/s."""
from bench.measure import share_of_peak, step_dev_ms
from bench.work import model


def read(run):
    ms = step_dev_ms(run, "train_step")
    if ms is None:
        return None
    t = run.traffic
    return share_of_peak(model.train_flops(run.arch, t["batch"], t["seq_len"]),
                         ms / 1e3, run.peaks["flops_per_s"])
