"""Granite 4.0-H (granite-4.0-h-micro) at its published widths: the
parameter count and the planning graph take each layer by its kind, 36
SSD layers with an MLP and 4 attention layers, so that the planner
prices the stack that runs."""
import pytest

from repro.configs import get_config
from repro.core.graph_builders import BYTES
from repro.models.registry import planning_graph


@pytest.fixture(scope="module")
def cfg():
    return get_config("granite_4_0_h_micro")


def test_param_count_is_the_published_size(cfg):
    # 3.19 B: 2.986 B in the stack, 205.5 M in the tied embedding
    assert cfg.param_count() == pytest.approx(3.19e9, rel=0.005)


def test_planning_graph_prices_each_block_by_its_kind(cfg):
    seq = 8192
    g = planning_graph(cfg, seq)
    blocks = [n for n in g.nodes if n.name.startswith("block")]
    assert len(blocks) == cfg.n_layers
    ssd_state = BYTES * 2 * cfg.d_model * cfg.ssm_state
    kv_state = BYTES * 2 * seq * cfg.n_kv_heads * cfg.hd
    kinds = ["attn" if i % 10 == 5 else "ssm" for i in range(cfg.n_layers)]
    assert [("ssm" if b.state_bytes == ssd_state else "attn") for b in blocks] == kinds
    assert all(b.state_bytes == kv_state for b, k in zip(blocks, kinds) if k == "attn")
    stack = cfg.param_count() - cfg.padded_vocab * cfg.d_model
    assert sum(b.param_bytes for b in blocks) == pytest.approx(BYTES * stack, rel=0.01)


def test_an_ssm_model_without_an_mlp_is_priced_as_before():
    cfg = get_config("mamba2_780m")
    g = planning_graph(cfg, 2048)
    blocks = [n for n in g.nodes if n.name.startswith("block")]
    d_in = 2 * cfg.d_model
    per = BYTES * (cfg.d_model * (2 * d_in + 2 * cfg.ssm_state) + d_in * cfg.d_model)
    assert len(blocks) == 48 and all(b.param_bytes == per for b in blocks)
