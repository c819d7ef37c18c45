"""Device milliseconds per step of the attention layers' attention in a
prefill step (the jitted prefill_step): the flash kernel, or the
query-chunked reference at short prompts; every op under the program's
attention scope."""
from bench.scopes import read_scope_ms


def read(run):
    return read_scope_ms(run, "prefill_step", "attention", "scope_ms.prefill.attention")
