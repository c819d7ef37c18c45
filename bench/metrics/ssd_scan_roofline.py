"""The SSD scan kernel's share of its roofline in prefill: one call per
layer per round."""
from bench.measure import roofline, serve_shape
from bench.work import ssd_scan


def read(run):
    a = run.arch
    B, P, _, rounds = serve_shape(run)
    din = a.get("ssm_expand", 2) * a["d_model"]
    p = a.get("ssm_headdim", 64)
    w = ssd_scan.work(B, P, din // p, p, a.get("ssm_ngroups", 1), a["ssm_state"],
                      a.get("ssm_chunk", 256))
    return roofline(run, "ssd_scan_roofline", "ssd_scan", [w] * rounds * a["n_layers"])
