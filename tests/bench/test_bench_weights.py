"""Seeded weights: every leaf of the program's tree has a role, the same
seed draws the same tree, and the reference redraws each leaf alone."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import model

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "tests/bench/data/tiny-moe.json").read_text())
BIG = 2**31 + 4242


@pytest.fixture(scope="module")
def built():
    from repro.models.transformer import ParallelCtx

    cfg = model.model_config(TINY)
    rcfg = model.runtime_config(TINY, balancer="ultraep", cf_pair=1.0,
                                cf_slot=34 / 8)
    pctx = ParallelCtx(mesh=None)
    return cfg, rcfg, pctx, model.make_params(BIG, cfg, rcfg, pctx)


def test_config_maps_the_published_keys(built):
    cfg = built[0]
    assert cfg.num_layers == 2 and cfg.moe.first_dense_layers == 1
    assert cfg.moe.score_fn == "sigmoid" and cfg.qkv_bias
    assert cfg.moe.shared_d_ff == 64 and cfg.moe.n_shared_experts == 1


def test_same_seed_same_weights(built):
    cfg, rcfg, pctx, p = built
    q = model.make_params(BIG, cfg, rcfg, pctx)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    r = model.make_params(BIG + 1, cfg, rcfg, pctx)
    assert not np.array_equal(np.asarray(p.lm_head), np.asarray(r.lm_head))


def test_reference_redraws_each_leaf(built):
    cfg, rcfg, pctx, p = built
    roles = model.leaf_roles(cfg, rcfg, p)
    names = set()
    for (role, layers, leaf), got in zip(roles, jax.tree.leaves(p)):
        name = role if layers is None else f"layer{layers[0]}.{role}"
        names.add(name)
        want = model.weight(BIG, name, leaf.shape, leaf.dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert {"embedding", "lm_head", "layer0.ffn.w2", "layer1.moe.w1",
            "layer1.moe.router", "layer1.attn.bq"} <= names
    assert len(names) == len(roles)


def test_scales_keep_the_residual_stream_at_unit_scale(built):
    p = built[3]
    emb = np.asarray(p.embedding, np.float32)
    assert 0.9 < emb.std() < 1.1
    head = np.asarray(p.lm_head, np.float32)
    assert head.std() == pytest.approx(128 ** -0.5, rel=0.1)
