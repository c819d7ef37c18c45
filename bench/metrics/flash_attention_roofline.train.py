"""The flash kernel's share of its roofline in training: the forward
kernel runs twice per layer and step under full remat (forward, and
again when the backward recomputes the layer); the backward is XLA."""
from bench.measure import roofline
from bench.work import flash_attention


def read(run):
    a, t = run.arch, run.traffic
    w = flash_attention.work(t["batch"], t["seq_len"], a["n_heads"], a["n_kv_heads"],
                             a.get("head_dim") or a["d_model"] // a["n_heads"], a.get("window"))
    calls = run.stats["steps"] * a["n_layers"] * (2 if t["remat"] == "full" else 1)
    return roofline(run, "flash_attention_roofline.train", "flash_attention",
                    [w] * calls)
