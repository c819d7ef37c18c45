"""Device milliseconds per step of the attention layers' attention in a
decode step (the jitted serve_step): ``decode_attention`` over each K/V
cache; every op under the program's attention scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "serve_step", "attention", "scope_ms.decode.attention")
