"""The decode-attention kernel's share of its roofline: one call per
layer per decode step, each over the live cache entries."""
from bench.measure import roofline, serve_shape
from bench.work import decode_attention


def read(run):
    a = run.arch
    B, P, G, rounds = serve_shape(run)
    hd = a.get("head_dim") or a["d_model"] // a["n_heads"]
    steps = [decode_attention.work(B, live, a["n_heads"], a["n_kv_heads"], hd)
             for live in decode_attention.live_entries(P, G, a.get("window"))]
    work = [w for w in steps for _ in range(a["n_layers"])] * rounds
    return roofline(run, "decode_attention_roofline", "decode_attention", work)
