"""Device milliseconds per step of the SSD layers' state update in a
decode step (the jitted serve_step): the recurrent update of each Mamba-2
mixer's state and its readout; every op under the program's scan
scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "serve_step", "scan", "scope_ms.decode.scan")
