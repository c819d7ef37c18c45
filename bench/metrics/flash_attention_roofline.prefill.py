"""The flash kernel's share of its roofline in prefill: one call per
layer per round, each over the whole batch of prompts."""
from bench.measure import roofline, serve_shape
from bench.work import flash_attention


def read(run):
    a = run.arch
    B, P, _, rounds = serve_shape(run)
    w = flash_attention.work(B, P, a["n_heads"], a["n_kv_heads"],
                             a.get("head_dim") or a["d_model"] // a["n_heads"], a.get("window"))
    return roofline(run, "flash_attention_roofline.prefill", "flash_attention",
                    [w] * rounds * a["n_layers"])
