"""Expert share: a MoE layer that holds one block of the router's experts.

The router keeps its width and top-k and renormalises over the k chosen
among all experts; the layer computes only the pairs whose expert it
holds.  Covered here: the shares of every block add up to the whole layer
(a shared expert, which every chip computes alike, counted once), in
prefill (a2a) and decode (replicated) dispatch; the counters account for
every routed pair, and a pair routed elsewhere is never a drop; a block
equal to the router traces exactly as a layer without one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEArch
from repro.core.balancer import BalancerConfig
from repro.models.model import decode_step, init_caches, init_lm, prefill_step
from repro.models.transformer import ParallelCtx, RuntimeConfig
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, MoEParams, init_moe_params, \
    moe_layer_local
from repro.moe.reference import swiglu

E, K, D, F, T = 16, 4, 32, 16, 48
BLOCK = 4                                   # experts each of 4 chips holds
PCTX = ParallelCtx(mesh=None)


def _cfg(mode="a2a", *, held=0, first=0, cap_slot=T * K, chunks=1):
    return MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=K, score_fn="softmax",
                            norm_topk_prob=True, held_experts=held,
                            first_expert=first),
        balancer=BalancerConfig(mode="ultraep", n_slot=2),
        d_model=D, d_ff=F, ep_size=1, cap_pair=T * K, cap_slot=cap_slot,
        n_shared_experts=1, shared_d_ff=F, dispatch_mode=mode,
        overlap_chunks=chunks)


def _block(p: MoEParams, first: int) -> MoEParams:
    s = slice(first, first + BLOCK)
    return p._replace(w1=p.w1[s], w3=p.w3[s], w2=p.w2[s])


@pytest.fixture(scope="module")
def layer():
    params = init_moe_params(jax.random.PRNGKey(0), _cfg())
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
    return params, x


def _host_ids(params, x):
    """Top-k experts of every token, recounted on the host."""
    logits = np.asarray(x, np.float64) @ np.asarray(params.router,
                                                    np.float64)
    return np.argsort(-logits, axis=1, kind="stable")[:, :K]


# ------------------------------------------------ shares add up ----------

@pytest.mark.parametrize("mode", ["a2a", "replicated"])
@pytest.mark.parametrize("chunks", [1, 2])
def test_four_shares_add_up_to_the_whole_layer(layer, mode, chunks):
    params, x = layer
    whole, _, _ = moe_layer_local(x, params, _cfg(mode, chunks=chunks),
                                  axis_name=None)
    parts = []
    for first in range(0, E, BLOCK):
        y, _, _ = moe_layer_local(
            x, _block(params, first),
            _cfg(mode, held=BLOCK, first=first, chunks=chunks),
            axis_name=None)
        parts.append(y)
    shared = swiglu(x, params.shared_w1, params.shared_w3, params.shared_w2)
    total = sum(parts) - (len(parts) - 1) * shared
    # float32 throughout; the parts sum each token's k contributions, and
    # the shared expert, in another order than the whole layer does, so
    # rounding of a few float32 ulps at the output's scale (up to ~4) is
    # all that may differ.  One expert left out moves outputs by ~1e-1.
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=0, atol=1e-5)
    # Each share is a real part: none is the whole layer on its own.
    for y in parts:
        assert float(jnp.abs(y - whole).max()) > 1e-2


# ------------------------------------------------ counters ----------------

@pytest.mark.parametrize("mode", ["a2a", "replicated"])
def test_pairs_routed_elsewhere_are_never_drops(layer, mode):
    """Slots too small for the block's load: the drops are exactly the
    block's overflow, and the pairs outside the block are not among them."""
    params, x = layer
    first, cap = 2 * BLOCK, 6
    _, _, st = moe_layer_local(
        x, _block(params, first),
        _cfg(mode, held=BLOCK, first=first, cap_slot=cap), axis_name=None)
    ids = _host_ids(params, x)
    load = np.bincount(ids.reshape(-1), minlength=E)
    np.testing.assert_array_equal(np.asarray(st.counts), load)
    here = load[first:first + BLOCK]
    overflow = int(np.maximum(here - cap, 0).sum())
    assert overflow > 0
    # At EP=1 the plan binds no replica: each held expert has one slot.
    assert int(st.drops_dispatch + st.drops_slot) == overflow
    assert int(here.sum()) < T * K      # some pairs are routed elsewhere


def _qwen_like(held=BLOCK, first=BLOCK) -> ModelConfig:
    """qwen3's layer kinds at toy widths: every layer MoE, softmax gate
    renormalised over the top-k, no shared expert, q/k norm, GQA."""
    return ModelConfig(
        name="tiny-qwen3", family="moe", num_layers=4, d_model=D,
        vocab_size=64, num_heads=8, num_kv_heads=2, head_dim=8,
        qk_norm=True, rope_theta=1e6,
        moe=MoEArch(num_experts=E, top_k=K, d_ff=F, score_fn="softmax",
                    norm_topk_prob=True, aux_loss_weight=0.0, n_slot=2,
                    held_experts=held, first_expert=first))


def _rcfg():
    return RuntimeConfig(balancer=BalancerConfig(mode="ultraep", n_slot=2),
                         cf_pair=1.0, cf_slot=(BLOCK + 2) / K, remat=False)


@pytest.mark.parametrize("valid", [16, 11])
def test_step_counters_account_for_every_routed_pair(valid):
    cfg, rcfg = _qwen_like(), _rcfg()
    params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, PCTX)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                              cfg.vocab_size)
    caches = init_caches(cfg, 1, 32, rcfg)
    _, caches, c = prefill_step(params, caches, toks, cfg, rcfg, PCTX,
                                valid_len=valid)
    # The whole chunk is routed, its right-padding too.
    np.testing.assert_array_equal(c.held + c.drops + c.absent, [16 * K] * 4)
    np.testing.assert_array_equal(c.drops, [0] * 4)      # dropless slots
    assert (np.asarray(c.absent) > 0).all() and (np.asarray(c.held) > 0).all()
    _, _, d = decode_step(params, caches, toks[:, :1], cfg, rcfg, PCTX)
    np.testing.assert_array_equal(d.held + d.drops + d.absent, [K] * 4)


# ------------------------------------------------ whole block ------------

@pytest.mark.parametrize("mode", ["a2a", "replicated"])
def test_block_of_the_whole_router_traces_as_without_one(layer, mode):
    params, x = layer

    def jaxpr(cfg, p=params):
        return str(jax.make_jaxpr(
            lambda x, p: moe_layer_local(x, p, cfg, axis_name=None))(x, p))

    plain = jaxpr(_cfg(mode))
    assert jaxpr(_cfg(mode, held=E, first=0)) == plain
    assert jaxpr(_cfg(mode, held=BLOCK, first=0), _block(params, 0)) != plain


def test_whole_router_step_differs_only_by_a_constant_absent():
    """A model step whose block is the whole router: the same program as
    with no block given, and its ``absent`` counter a constant zero."""
    whole = _qwen_like(held=E, first=0)
    plain = _qwen_like(held=0, first=0)
    rcfg = _rcfg()
    params = init_lm(jax.random.PRNGKey(0), plain, rcfg, PCTX)
    caches = init_caches(plain, 1, 32, rcfg)
    toks = jnp.zeros((1, 16), jnp.int32)

    def jaxpr(cfg):
        return jax.make_jaxpr(lambda p, c, t: prefill_step(
            p, c, t, cfg, rcfg, PCTX, valid_len=16))(params, caches, toks)

    a, b = jaxpr(whole), jaxpr(plain)
    assert str(a) == str(b)
    absent = jax.tree.leaves(a.out_avals)[-1]
    assert absent.shape == (4,)
    _, _, c = prefill_step(params, caches, toks, plain, rcfg, PCTX,
                           valid_len=16)
    np.testing.assert_array_equal(c.absent, [0] * 4)


@pytest.mark.parametrize("held,first", [(17, 0), (4, 13), (-1, 0)])
def test_block_must_lie_inside_the_router(held, first):
    with pytest.raises(ValueError, match="outside"):
        MoEArch(num_experts=E, top_k=K, d_ff=F, held_experts=held,
                first_expert=first)
    with pytest.raises(ValueError, match="outside"):
        GatingConfig(num_experts=E, top_k=K, held_experts=held,
                     first_expert=first)


def test_share_needs_flat_ep():
    with pytest.raises(ValueError, match="flat EP"):
        MoEConfig(gating=GatingConfig(num_experts=E, top_k=K,
                                      held_experts=BLOCK),
                  balancer=BalancerConfig(n_slot=2), d_model=D, d_ff=F,
                  ep_size=2, cap_pair=8, cap_slot=8, racks=2)


# ------------------------------------- shares over an EP group of 2 ------

_EP2_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.balancer import BalancerConfig
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local

R, E, K, D, F = 2, 16, 4, 16, 24
T = 32 * R
mesh = Mesh(np.array(jax.devices()[:R]), ("model",))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))

def layer(mode, held, first):
    cfg = MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=K, held_experts=held,
                            first_expert=first),
        balancer=BalancerConfig(mode="ultraep", n_slot=2),
        d_model=D, d_ff=F, ep_size=R, cap_pair=T * K, cap_slot=T * K,
        dispatch_mode=mode)
    s = slice(first, first + (held or E))
    tok = "model" if mode == "a2a" else None
    def run(x, router, w1, w3, w2):
        y, _, st = moe_layer_local(x, MoEParams(router, w1, w3, w2), cfg,
                                   axis_name="model")
        return y, (st.drops_dispatch + st.drops_slot)[None]
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P(tok, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(tok, None), P("model")))
    y, drops = jax.jit(f)(x, router, w1[s], w3[s], w2[s])
    assert int(drops.sum()) == 0, (mode, held, first)
    return np.asarray(y)

for mode in ("a2a", "replicated"):
    whole = layer(mode, 0, 0)
    parts = layer(mode, 8, 0) + layer(mode, 8, 8)
    err = np.abs(parts - whole).max()
    assert err < 1e-5, (mode, err)
print("EP2-SHARES-OK")
"""


def test_shares_add_up_over_an_ep_group_of_two():
    """Two blocks of 8, each spread over 2 EP ranks with real collectives
    (a2a and replicated), add up to the whole 16-expert layer."""
    from tests.helpers import run_multidevice

    assert "EP2-SHARES-OK" in run_multidevice(_EP2_SNIPPET, n_devices=2)
