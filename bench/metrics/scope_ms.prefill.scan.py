"""Device milliseconds per step of the SSD layers' scan in a prefill step
(the jitted prefill_step): dt, the decay, the ``ssd_scan`` kernel and the
skip term of every Mamba-2 mixer; every op under the program's scan
scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "prefill_step", "scan", "scope_ms.prefill.scan")
