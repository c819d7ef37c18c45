"""A decode step's model FLOPs, averaged over the round's positions, over
the mean device time of one decode step (the jitted ``serve_step``,
traced window) and the chip's peak FLOP/s."""
import numpy as np

from bench.measure import serve_shape, share_of_peak, step_dev_ms
from bench.work import decode_attention, model


def read(run):
    ms = step_dev_ms(run, "serve_step")
    if ms is None:
        return None
    a = run.arch
    B, P, G, _ = serve_shape(run)
    flops = np.mean([model.decode_flops(a, B, live)
                     for live in decode_attention.live_entries(P, G, a.get("window"))])
    return share_of_peak(flops, ms / 1e3, run.peaks["flops_per_s"])
