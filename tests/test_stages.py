"""Staged MoE execution pipeline + chunked overlap (DESIGN.md S11).

The load-bearing contract: with ``overlap_chunks = N`` the dispatch ->
compute -> combine tail runs once per token chunk against ONE plan solved
on the full-batch load, and at zero-drop capacities the chunked output is
**bit-identical** to the unchunked layer -- per-expert occurrence offsets
(:func:`repro.moe.stages.chunk_occ_offsets`) continue the global occurrence
index across chunks, so every item routes to the exact same expert
instance and per-chunk traffic is a subset of the unchunked traffic.

Covered here: config validation, single-rank bit-identity for all three
dispatch modes x 2/4 chunks, gradients, drop accounting under tight caps,
replica slots that carry tokens (4 ranks emulated by ``vmap``), the
chunking helpers, and real-collective identity on flat 8-rank and factored
(2 racks x 4 lanes) meshes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.balancer import BalancerConfig
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, init_moe_params, moe_layer_local
from repro.moe.stages import chunk_bounds, chunk_occ_offsets
from tests.helpers import run_multidevice

E, K, D, F, T = 8, 2, 16, 32, 64


def _cfg(mode="ultraep", **kw):
    return MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=K),
        balancer=BalancerConfig(mode=mode, n_slot=2),
        d_model=D, d_ff=F, ep_size=1,
        cap_pair=T * K, cap_slot=T * K, **kw)


@pytest.fixture
def setup():
    cfg = _cfg()
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    return cfg, params, x


# ------------------------------------------------- config validation ----

def test_rejects_zero_overlap_chunks():
    with pytest.raises(ValueError, match="overlap_chunks"):
        _cfg(overlap_chunks=0)


def test_rejects_negative_distribute_chunks():
    with pytest.raises(ValueError, match="distribute_chunks"):
        _cfg(distribute_chunks=0)


def test_rejects_indivisible_chunk_count(setup):
    _, params, x = setup
    cfg = _cfg(overlap_chunks=3)           # 64 % 3 != 0: caught at trace time
    with pytest.raises(ValueError, match="must divide"):
        moe_layer_local(x, params, cfg, axis_name=None)


# ------------------------------------- single-rank chunked == unchunked --

@pytest.mark.parametrize("mode", ["a2a", "hier_a2a", "replicated"])
@pytest.mark.parametrize("chunks", [2, 4])
def test_overlap_bit_identical_to_unchunked(mode, chunks, setup):
    """At zero-drop capacities every dispatch mode is bitwise unchanged by
    chunking -- same plan, same instance per item, same combine order."""
    _, params, x = setup
    y0, aux0, s0 = moe_layer_local(
        x, params, _cfg(dispatch_mode=mode), axis_name=None)
    y1, aux1, s1 = moe_layer_local(
        x, params, _cfg(dispatch_mode=mode, overlap_chunks=chunks),
        axis_name=None)
    assert int(s0.drops_dispatch) == 0 and int(s0.drops_slot) == 0
    assert int(s1.drops_dispatch) == 0 and int(s1.drops_slot) == 0
    assert np.array_equal(np.array(y0), np.array(y1)), (
        mode, chunks, np.abs(np.array(y0) - np.array(y1)).max())
    assert np.array_equal(np.array(aux0), np.array(aux1))


def test_overlap_bit_identical_under_jit(setup):
    """jit(chunked) == jit(unchunked): the pipelined unrolled loop fuses
    into one XLA program without reassociating the combine."""
    _, params, x = setup

    def f(cfg):
        return jax.jit(lambda x: moe_layer_local(
            x, params, cfg, axis_name=None)[0])(x)

    y0 = f(_cfg())
    y1 = f(_cfg(overlap_chunks=2))
    assert np.array_equal(np.array(y0), np.array(y1))


def test_overlap_gradients_match(setup):
    """Gradients are allclose (not bitwise: the weight-grad einsum
    reassociates the token sum across chunk boundaries)."""
    _, params, x = setup

    def loss(p, cfg):
        y, aux, _ = moe_layer_local(x, p, cfg, axis_name=None)
        return (y ** 2).sum() + aux

    g0 = jax.grad(loss)(params, _cfg())
    g1 = jax.grad(loss)(params, _cfg(overlap_chunks=2))
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.array(a), np.array(b),
                                   rtol=1e-5, atol=1e-6)


def test_overlap_tight_caps_counts_drops(setup):
    """Under a starved slot capacity the chunked layer still produces
    finite output and accounts its drops (summed over chunks)."""
    _, params, x = setup
    cfg = MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=K),
        balancer=BalancerConfig(mode="none", n_slot=2),
        d_model=D, d_ff=F, ep_size=1, cap_pair=T * K, cap_slot=4,
        overlap_chunks=2)
    y, _, stats = moe_layer_local(x, params, cfg, axis_name=None)
    assert np.isfinite(np.array(y)).all()
    assert int(stats.drops_slot) > 0
    assert int(stats.max_slot_load) <= 4


def test_overlap_stats_match_unchunked_at_zero_drop(setup):
    _, params, x = setup
    _, _, s0 = moe_layer_local(x, params, _cfg(), axis_name=None)
    _, _, s1 = moe_layer_local(x, params, _cfg(overlap_chunks=2),
                               axis_name=None)
    assert np.array_equal(np.array(s0.counts), np.array(s1.counts))
    assert int(s0.pre_max) == int(s1.pre_max)
    assert int(s0.post_max) == int(s1.post_max)
    # Per-chunk slot occupancy can only be <= the unchunked occupancy.
    assert int(s1.max_slot_load) <= int(s0.max_slot_load)


# ------------------------------------- replica slots carry the tokens ----

_EP = 4


def _ep_cfg(mode, **kw):
    return MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=K),
        balancer=BalancerConfig(mode=mode, n_slot=2),
        d_model=64, d_ff=64, ep_size=_EP, cap_pair=T * K,
        cap_slot=T * K * _EP, **kw)


@pytest.fixture(scope="module")
def hot_ep():
    """(router, x, (w1, w3, w2)) for 4 ranks, experts 3 and 5 hot."""
    cfg = _ep_cfg("ultraep")
    D, F, epr = cfg.d_model, cfg.d_ff, E // _EP
    pk = jax.random.split(jax.random.PRNGKey(0), 5)
    router = (jax.random.normal(pk[0], (D, E)) * D ** -0.5
              ).at[:, 3].add(0.3).at[:, 5].add(0.2)
    x = jax.random.normal(pk[1], (_EP, T, D))
    ws = (jax.random.normal(pk[2], (_EP, epr, D, F)) * D ** -0.5,
          jax.random.normal(pk[3], (_EP, epr, D, F)) * D ** -0.5,
          jax.random.normal(pk[4], (_EP, epr, F, D)) * F ** -0.5)
    return router, x, ws


def _run_ep(cfg, router, x, ws):
    """The staged layer on ``_EP`` ranks, emulated by ``vmap`` over a named
    axis: every collective of the layer runs over the batch of ranks."""
    from repro.moe.layer import MoEParams

    def body(x, w1, w3, w2):
        y, _, st = moe_layer_local(x, MoEParams(router, w1, w3, w2), cfg,
                                   axis_name="model")
        return y, st.drops_dispatch + st.drops_slot

    return jax.jit(jax.vmap(body, axis_name="model"))(x, *ws)


@pytest.mark.parametrize("variant", ["einsum", "kernel", "int8",
                                     "replicated"])
def test_replica_slots_match_replica_free_path(variant, hot_ep, request):
    """A hot expert fills both replica slots of a rank with tokens; the
    layer's output equals the replica-free path's (every token on its home
    main) bit for bit at zero-drop capacities, so the replica half of
    ComputeStage reads exactly the mains' weights.  The w8a8 path also
    stays within its tolerance of the fp replica-free output.  The
    replicated (decode) dispatch gives every rank all the tokens and
    merges ranks by a psum, which reassociates: float32 rounding there."""
    from repro.moe import stages

    router, x, ws = hot_ep
    kw = {"kernel": {"use_kernel": True}, "int8": {"ffn_dtype": "int8"},
          "replicated": {"dispatch_mode": "replicated"}, "einsum": {}
          }[variant]
    if variant == "replicated":
        x = jnp.broadcast_to(x.reshape(1, -1, x.shape[-1]),
                             (_EP, _EP * T, x.shape[-1]))
    if variant == "kernel":
        request.getfixturevalue("tpu_interpret")
    cfg = _ep_cfg("ultraep", **kw)
    ctx = stages.make_stage_ctx(cfg, "model")

    def replica_loads(x):
        gs = stages.gate_stage(ctx, x, router)
        ps = stages.plan_stage(ctx, gs)
        ds = stages.dispatch_stage(ctx, x, gs.gate_out.expert_ids, gs, ps)
        return ds.valid[cfg.layout.experts_per_rank:].sum(axis=-1)

    loads = np.asarray(
        jax.jit(jax.vmap(replica_loads, axis_name="model"))(x))
    assert (loads > 0).all(axis=1).any(), loads
    if variant == "kernel":
        jaxpr = jax.make_jaxpr(lambda *a: _run_ep(cfg, router, *a))(x, ws)
        assert "pallas_call" in str(jaxpr)

    y, drops = _run_ep(cfg, router, x, ws)
    y0, drops0 = _run_ep(_ep_cfg("none", **kw), router, x, ws)
    assert not np.asarray(drops).any() and not np.asarray(drops0).any()
    y, y0 = np.asarray(y), np.asarray(y0)
    if variant == "replicated":
        # The rank merge is a psum of per-rank partial sums: a token whose
        # contributions the plan moves to a replica's rank sums them in
        # another order, so the outputs agree to float32 rounding.
        np.testing.assert_allclose(y, y0, rtol=1e-6,
                                   atol=1e-6 * np.abs(y0).max())
    else:
        assert np.array_equal(y, y0), (variant, np.abs(y - y0).max())
    if variant == "int8":
        yf = np.asarray(_run_ep(_ep_cfg("none"), router, x, ws)[0])
        scale = np.abs(yf).max()
        assert np.allclose(y, yf, rtol=1e-2, atol=3e-2 * scale)


# --------------------------------------------------- chunking helpers ---

def test_chunk_bounds_equal_split():
    assert chunk_bounds(64, n_chunks=4) == [(0, 16), (16, 16), (32, 16),
                                            (48, 16)]
    assert chunk_bounds(64, n_chunks=1) == [(0, 64)]


def test_chunk_bounds_fixed_size_ragged_tail():
    assert chunk_bounds(10, chunk_size=4) == [(0, 4), (4, 4), (8, 2)]
    assert chunk_bounds(8, chunk_size=4) == [(0, 4), (4, 4)]
    assert chunk_bounds(3, chunk_size=8) == [(0, 3)]


def test_chunk_bounds_rejects_bad_args():
    with pytest.raises(ValueError, match="exactly one"):
        chunk_bounds(8)
    with pytest.raises(ValueError, match="exactly one"):
        chunk_bounds(8, n_chunks=2, chunk_size=4)
    with pytest.raises(ValueError, match="divide"):
        chunk_bounds(10, n_chunks=3)
    with pytest.raises(ValueError, match="chunk_size"):
        chunk_bounds(8, chunk_size=0)


def test_chunk_occ_offsets_continue_global_index():
    """offset[c, e] == number of e-items in chunks < c, so per-chunk local
    occurrence + offset reproduces the global occurrence index."""
    ids = jnp.array([[0, 1], [1, 1], [0, 2], [1, 0]], jnp.int32)  # T=4, k=2
    off = np.array(chunk_occ_offsets(ids, 2, 3))
    # chunk 0 holds ids {0,1,1,1}; chunk 1 sees 1 zero, 3 ones, 0 twos.
    assert np.array_equal(off, [[0, 0, 0], [1, 3, 0]])
    assert np.array_equal(off[0], np.zeros(3))


# ------------------------------ real collectives: flat 8-rank overlap ----

requires8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@requires8
def test_overlap_bitwise_on_flat_mesh_inprocess():
    """8-rank flat mesh: chunked a2a dispatch (real all_to_all per chunk)
    is bit-identical to the unchunked layer."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.moe.layer import MoEParams

    R = 8
    EE, kk, DD, FF = 2 * R, 4, 16, 24
    TT = 16 * R
    devs = np.array(jax.devices()[:R])
    mesh = Mesh(devs.reshape(R), ("model",))
    pk = jax.random.split(jax.random.PRNGKey(0), 5)
    router = jax.random.normal(pk[0], (DD, EE), jnp.float32) * DD ** -0.5
    w1 = jax.random.normal(pk[1], (EE, DD, FF)) * DD ** -0.5
    w3 = jax.random.normal(pk[2], (EE, DD, FF)) * DD ** -0.5
    w2 = jax.random.normal(pk[3], (EE, FF, DD)) * FF ** -0.5
    x = jax.random.normal(pk[4], (TT, DD))

    def run_case(overlap):
        cfg = MoEConfig(
            gating=GatingConfig(num_experts=EE, top_k=kk),
            balancer=BalancerConfig(mode="ultraep", n_slot=2),
            d_model=DD, d_ff=FF, ep_size=R, cap_pair=TT * kk,
            cap_slot=TT * kk, overlap_chunks=overlap)

        def run(x, router, w1, w3, w2):
            y, _, stats = moe_layer_local(
                x, MoEParams(router, w1, w3, w2), cfg, axis_name="model")
            return y, (stats.drops_dispatch + stats.drops_slot)[None]

        f = jax.shard_map(
            run, mesh=mesh, check_vma=False,
            in_specs=(P("model", None), P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(P("model", None), P("model")))
        y, drops = jax.jit(f)(x, router, w1, w3, w2)
        assert int(drops.sum()) == 0
        return np.array(y)

    y0 = run_case(1)
    y2 = run_case(2)
    assert np.array_equal(y0, y2), np.abs(y0 - y2).max()


# --------------------------- real collectives: factored 2x4 rack mesh ----

_OVERLAP_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.balancer import BalancerConfig
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local

RACKS, LANES = 2, 4
R = RACKS * LANES
E, kk, D, F = 2 * R, 4, 16, 24
T = 32 * R
devs = np.array(jax.devices()[:R])
mesh = Mesh(devs.reshape(RACKS, LANES), ("rack", "model"))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))
gcfg = GatingConfig(num_experts=E, top_k=kk)

def run_case(mode, overlap, tok_spec):
    cfg = MoEConfig(gating=gcfg,
                    balancer=BalancerConfig(mode="ultraep", n_slot=2),
                    d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk,
                    cap_slot=T*kk, dispatch_mode=mode, racks=RACKS,
                    overlap_chunks=overlap)
    def run(x, router, w1, w3, w2):
        y, _, stats = moe_layer_local(
            x, MoEParams(router, w1, w3, w2), cfg,
            axis_name=("rack", "model"))
        return y, (stats.drops_dispatch + stats.drops_slot)[None]
    ep = ("rack", "model")
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P(tok_spec, None), P(None, None), P(ep, None, None),
                  P(ep, None, None), P(ep, None, None)),
        out_specs=(P(tok_spec, None), P(ep)))
    y, drops = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0, (mode, overlap)
    return np.array(y)

for mode, tok_spec in (("hier_a2a", ("rack", "model")),
                       ("replicated", None)):
    y0 = run_case(mode, 1, tok_spec)
    y2 = run_case(mode, 2, tok_spec)
    assert np.array_equal(y0, y2), (
        mode, np.abs(y0 - y2).max(), "chunked != unchunked")
print("OVERLAP-BITWISE-OK")
"""


def test_overlap_bitwise_on_rack_mesh():
    """(2 racks x 4 lanes): chunked two-hop dispatch and chunked replicated
    decode both match their unchunked runs bit for bit."""
    out = run_multidevice(_OVERLAP_SNIPPET)
    assert "OVERLAP-BITWISE-OK" in out
