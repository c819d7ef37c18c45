"""Device milliseconds per step of the mixer's ops in a decode step (the
jitted serve_step): pre-norm, projections, cache write, attention or the
SSD state update, output projection and residual; every op under the
program's mixer scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "serve_step", "mixer", "scope_ms.decode.mixer")
