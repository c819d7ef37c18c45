"""Device milliseconds per step of the mixer's ops in a prefill step (the
jitted prefill_step): pre-norm, projections, flash attention or the SSD
scan, cache fill, output projection and residual; every op under the
program's mixer scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "prefill_step", "mixer", "scope_ms.prefill.mixer")
