"""Device milliseconds per step of the layer scan's own ops in a decode
step (the jitted serve_step): slicing each layer's weights and cache out
of the stack, stacking the new cache back, the carry's copies, and the
copy of the scan's stacked output that the compiler adds after the loop;
every op under the program's layers scope and under no mixer, mlp or moe,
and every top-level copy of a value the scan made."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "serve_step", "layers_self", "scope_ms.decode.layers_self")
