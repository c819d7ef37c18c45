"""The prefill's model FLOPs over the device time of one prefill step
(the jitted ``prefill_step``, traced window) and the chip's peak FLOP/s."""
from bench.measure import serve_shape, share_of_peak, step_dev_ms
from bench.work import model


def read(run):
    ms = step_dev_ms(run, "prefill_step")
    if ms is None:
        return None
    B, P, _, _ = serve_shape(run)
    return share_of_peak(model.prefill_flops(run.arch, B, P), ms / 1e3, run.peaks["flops_per_s"])
