"""Model-level work of a step, from the configuration's sizes: the FLOPs
the step needs (two per multiply-add of every weight a token passes
through, plus attention or the SSD scan; recomputation not counted) and
the bytes a decode step must move (each weight read once, the live
cache or recurrent state read once, and what the step writes to it)."""
from __future__ import annotations

from . import flash_attention, ssd_scan


def _hd(a: dict) -> int:
    return a.get("head_dim") or a["d_model"] // a["n_heads"]


def body_weights(a: dict) -> float:
    """Weights of the layer stack that every token multiplies."""
    d, L = a["d_model"], a["n_layers"]
    if a.get("ssm"):
        din = a.get("ssm_expand", 2) * d
        h = din // a.get("ssm_headdim", 64)
        gn = a.get("ssm_ngroups", 1) * a["ssm_state"]
        return L * (d * (2 * din + 2 * gn + h) + din * d)
    hd = _hd(a)
    attn = d * hd * (2 * a["n_heads"] + 2 * a["n_kv_heads"])
    return L * (attn + 3 * d * a["d_ff"])


def head_weights(a: dict) -> float:
    return a["d_model"] * a["vocab_size"]


def _mixer_flops(a: dict, batch: int, seq: int) -> float:
    """Attention or SSD FLOPs of a whole prompt, all layers."""
    L = a["n_layers"]
    if a.get("ssm"):
        din = a.get("ssm_expand", 2) * a["d_model"]
        p = a.get("ssm_headdim", 64)
        return L * ssd_scan.work(batch, seq, din // p, p, a.get("ssm_ngroups", 1),
                                 a["ssm_state"], a.get("ssm_chunk", 256))[0]
    return L * flash_attention.work(batch, seq, a["n_heads"], a["n_kv_heads"],
                                    _hd(a), a.get("window"))[0]


def prefill_flops(a: dict, batch: int, seq: int) -> float:
    """A prompt's forward pass; the head runs on the last position only."""
    return (2.0 * body_weights(a) * batch * seq + _mixer_flops(a, batch, seq)
            + 2.0 * head_weights(a) * batch)


def train_flops(a: dict, batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) with the head on every
    position."""
    fwd = (2.0 * (body_weights(a) + head_weights(a)) * batch * seq
           + _mixer_flops(a, batch, seq))
    return 3.0 * fwd


def _state_bytes(a: dict, batch: int) -> float:
    """Mamba-2's recurrent state (f32) and conv tail, per layer."""
    din = a.get("ssm_expand", 2) * a["d_model"]
    conv = din + 2 * a.get("ssm_ngroups", 1) * a["ssm_state"]
    return batch * (4.0 * din * a["ssm_state"] + 2.0 * (a.get("ssm_conv", 4) - 1) * conv)


def decode_flops(a: dict, batch: int, live: int) -> float:
    """One decode step with ``live`` cache entries per sequence."""
    w = 2.0 * (body_weights(a) + head_weights(a)) * batch
    L = a["n_layers"]
    if a.get("ssm"):
        din = a.get("ssm_expand", 2) * a["d_model"]
        return w + L * batch * 4.0 * din * a["ssm_state"]
    return w + L * 4.0 * batch * a["n_heads"] * _hd(a) * live


def decode_bytes(a: dict, batch: int, live: int, itemsize: int = 2) -> float:
    """Bytes one decode step must move: every weight once, the embedding
    rows of the batch, the live cache read and the new entry written (or
    the recurrent state read and written)."""
    d, L = a["d_model"], a["n_layers"]
    weights = itemsize * (body_weights(a) + head_weights(a) + batch * d)
    if a.get("ssm"):
        return weights + 2.0 * L * _state_bytes(a, batch)
    kv = itemsize * 2.0 * a["n_kv_heads"] * _hd(a) * batch
    return weights + L * kv * (live + 1)
