"""Fused single-sort dispatch engine vs the reference multi-sort path.

The contract (DESIGN.md S2): at capacities sized for zero drops, the fused
engine is **bit-identical** to the reference scatter path
(:func:`repro.moe.dispatch.moe_layer_reference`) for the full MoE layer --
same buffers' contents per slot, row-independent grouped FFN, and a combine
that folds the k contributions of each token in the same order.  The
reference itself is pinned to the dense per-token oracle.  At tight
capacities both paths drop, and the fused path's accounting must conserve
items end to end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import balancer as bal
from repro.core.balancer import BalancerConfig
from repro.core.layout import ExpertLayout, physical_slot_of
from repro.core.planner import occurrence_index
from repro.moe import permute as fp
from repro.moe.dispatch import (
    bucket_by_slot,
    combine_tokens,
    dispatch_tokens,
    moe_layer_reference,
    unbucket,
)
from repro.moe.gating import GatingConfig, gate
from repro.moe.layer import MoEConfig, init_moe_params, moe_layer_local
from repro.moe.reference import moe_ref

E, D, F, T = 8, 16, 32, 64

MODES = ["none", "ultraep", "eplb_plus"]


def _cfg(mode, *, top_k=2, cap_pair=None, cap_slot=None, **kw):
    return MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=top_k),
        balancer=BalancerConfig(mode=mode, n_slot=2),
        d_model=D, d_ff=F, ep_size=1,
        cap_pair=T * top_k if cap_pair is None else cap_pair,
        cap_slot=T * top_k if cap_slot is None else cap_slot, **kw)


def _layer(cfg, seed=0):
    params = init_moe_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, D))
    return params, x


# ------------------------------------------------- layer equivalence ----

@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("top_k", [2, 3])
@pytest.mark.parametrize("mode", MODES)
def test_fused_layer_bitwise_equals_reference(mode, top_k, seed):
    """Zero-drop capacities: fused == reference, bit for bit."""
    cfg = _cfg(mode, top_k=top_k)
    params, x = _layer(cfg, seed)
    y_f, aux_f, st_f = moe_layer_local(x, params, cfg, axis_name=None)
    y_r, aux_r, st_r = moe_layer_reference(x, params, cfg, axis_name=None)
    assert int(st_f.drops_dispatch) == 0 and int(st_f.drops_slot) == 0
    assert int(st_r.drops_dispatch) == 0 and int(st_r.drops_slot) == 0
    assert np.array_equal(np.array(y_f), np.array(y_r))
    assert np.array_equal(np.array(aux_f), np.array(aux_r))
    assert int(st_f.max_slot_load) == int(st_r.max_slot_load)


@pytest.mark.parametrize("mode", MODES)
def test_fused_replicated_bitwise_equals_reference(mode):
    cfg = _cfg(mode, dispatch_mode="replicated")
    params, x = _layer(cfg)
    y_f, _, st_f = moe_layer_local(x, params, cfg, axis_name=None)
    y_r, _, st_r = moe_layer_reference(x, params, cfg, axis_name=None)
    assert int(st_f.drops_slot) == 0 and int(st_r.drops_slot) == 0
    assert np.array_equal(np.array(y_f), np.array(y_r))


def test_fused_gradients_match_reference():
    cfg = _cfg("ultraep")
    params, x = _layer(cfg)

    def loss(layer):
        def f(x):
            y, aux, _ = layer(x, params, cfg, axis_name=None)
            return (y ** 2).sum() + aux
        return f

    g_f = jax.grad(loss(moe_layer_local))(x)
    g_r = jax.grad(loss(moe_layer_reference))(x)
    np.testing.assert_allclose(np.array(g_f), np.array(g_r), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("dispatch_mode", ["a2a", "replicated"])
def test_reference_layer_matches_dense_oracle(dispatch_mode):
    """Zero-drop capacities: the multi-sort oracle computes the dense
    per-token MoE of :func:`repro.moe.reference.moe_ref`."""
    cfg = _cfg("ultraep", dispatch_mode=dispatch_mode, n_shared_experts=1,
               shared_d_ff=F)
    params, x = _layer(cfg)
    y, _, stats = moe_layer_reference(x, params, cfg, axis_name=None)
    assert int(stats.drops_dispatch) == 0 and int(stats.drops_slot) == 0
    g = gate(x, params.router, cfg.gating)
    y_ref = moe_ref(x, g.expert_ids, g.weights, params.w1, params.w3,
                    params.w2, shared=(params.shared_w1, params.shared_w3,
                                       params.shared_w2))
    np.testing.assert_allclose(np.array(y), np.array(y_ref), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------- multi-rank (simulated) -----

@pytest.mark.parametrize("mode", MODES)
def test_fused_engine_multirank_bitwise(mode):
    """R=4 engine-level equivalence with a manual all_to_all transpose."""
    R, kk, Tl = 4, 4, 48
    gcfg = GatingConfig(num_experts=16, top_k=kk)
    layout = ExpertLayout(16, R, 2)
    home = layout.home()
    num_slots = layout.slots_per_rank
    w = jax.random.normal(jax.random.PRNGKey(0), (D, 16))
    xs_rank = [jax.random.normal(jax.random.PRNGKey(10 + r), (Tl, D))
               for r in range(R)]
    gos = [gate(x, w, gcfg) for x in xs_rank]
    lam = jnp.stack([g.counts for g in gos])
    plan = bal.solve(lam, home, BalancerConfig(mode=mode, n_slot=2))
    slot_of_all = physical_slot_of(layout, plan.x)
    cap_pair, cap_slot = Tl * kk, Tl * kk * R

    def a2a(rows):  # transpose the (src, dst) buffer grid
        return [jnp.stack([rows[s][d] for s in range(R)]) for d in range(R)]

    def fake_ffn(buf, valid):  # row-local stand-in for the grouped FFN
        return jnp.where(valid[:, :, None], buf * 2.0 + 1.0, 0)

    # Reference path.
    disps = [dispatch_tokens(xs_rank[r], gos[r].expert_ids, plan.q[r],
                             cap_pair=cap_pair) for r in range(R)]
    rx, re = a2a([d.send_x for d in disps]), a2a([d.send_e for d in disps])
    buck = [bucket_by_slot(rx[d], re[d], slot_of_all[d], num_slots=num_slots,
                           cap_slot=cap_slot) for d in range(R)]
    rets = a2a([unbucket(fake_ffn(b[0], b[1]), b[1], b[2],
                         (R, cap_pair, D)) for b in buck])
    y_ref = [combine_tokens(rets[s], disps[s], gos[s].weights, Tl)
             for s in range(R)]

    # Fused path.
    fds = [fp.fused_dispatch(xs_rank[r], gos[r].expert_ids, plan.cum_q[r],
                             slot_of_all, num_slots=num_slots,
                             cap_pair=cap_pair) for r in range(R)]
    rx_f = a2a([f.send_x for f in fds])
    rc_f = a2a([f.send_counts for f in fds])
    bks = [fp.fused_bucket(rx_f[d], rc_f[d], num_slots=num_slots,
                           cap_slot=cap_slot) for d in range(R)]
    rets_f = a2a([fp.fused_unbucket(fake_ffn(b[0], b[1]), b[2]) for b in bks])
    y_fus = [fp.fused_combine(rets_f[s], fds[s], gos[s].weights)
             for s in range(R)]

    for r in range(R):
        assert int(disps[r].drops) == 0 and int(fds[r].drops) == 0
        assert int(buck[r][3]) == 0 and int(bks[r][3]) == 0
        assert np.array_equal(np.array(y_ref[r]), np.array(y_fus[r]))


@pytest.mark.parametrize("cap_slot", [None, 4])
@pytest.mark.parametrize("mode", ["a2a", "replicated"])
def test_combine_never_reads_rows_past_the_counts(mode, cap_slot):
    """The count-aware FFN leaves the rows past each slot's count
    unwritten: poisoned with NaN there, the slot outputs combine to the
    same tokens.  Expert 0 is never routed, so slot 0 is empty and every
    dropped item's placeholder gather (slot 0, row 0) reads poison."""
    from repro.moe import stages

    cfg = _cfg("ultraep", dispatch_mode=mode, cap_slot=cap_slot)
    params, x = _layer(cfg)
    params = params._replace(router=params.router.at[:, 0].set(-10.0))
    x = jnp.abs(x)
    ctx = stages.make_stage_ctx(cfg, None)

    @jax.jit
    def front(params, x):
        gs = stages.gate_stage(ctx, x, params.router)
        ps = stages.plan_stage(ctx, gs)
        dist = stages.distribute_stage(ctx, params, gs, ps)
        ds = stages.dispatch_stage(ctx, x, gs.gate_out.expert_ids, gs, ps)
        outs, _ = stages.compute_stage(ctx, ds, dist)
        return ds._replace(max_rows=None), outs, gs.gate_out.weights

    # One program combines both, so only the rows it reads can differ.
    combine = jax.jit(lambda ds, outs, w: stages.combine_stage(ctx, ds,
                                                               outs, w))
    ds, outs, w = front(params, x)
    assert not ds.valid[0].any()
    assert (int(ds.drops_slot) > 0) == (cap_slot is not None)
    n_main = outs[0].shape[0]
    masks = (ds.valid[:n_main], ds.valid[n_main:])
    poisoned = tuple(jnp.where(v[..., None], o, jnp.nan)
                     for o, v in zip(outs, masks))
    y = np.asarray(combine(ds, poisoned, w))
    assert np.isfinite(y).all()
    assert np.array_equal(y, np.asarray(combine(ds, outs, w)))


# ------------------------------------------------- drop accounting ------

def test_fused_drop_accounting_tight_caps():
    """Every routing item is either bucketed or counted dropped, never lost."""
    R, kk, Tl = 4, 4, 48
    gcfg = GatingConfig(num_experts=16, top_k=kk)
    layout = ExpertLayout(16, R, 2)
    num_slots = layout.slots_per_rank
    w = jax.random.normal(jax.random.PRNGKey(0), (D, 16))
    xs_rank = [jax.random.normal(jax.random.PRNGKey(10 + r), (Tl, D))
               for r in range(R)]
    gos = [gate(x, w, gcfg) for x in xs_rank]
    lam = jnp.stack([g.counts for g in gos])
    plan = bal.solve(lam, layout.home(), BalancerConfig(mode="ultraep",
                                                        n_slot=2))
    slot_of_all = physical_slot_of(layout, plan.x)
    cap_pair, cap_slot = 24, 40  # deliberately lossy

    fds = [fp.fused_dispatch(xs_rank[r], gos[r].expert_ids, plan.cum_q[r],
                             slot_of_all, num_slots=num_slots,
                             cap_pair=cap_pair) for r in range(R)]
    pair_kept = sum(int(f.item_kept.sum()) for f in fds)
    pair_drops = sum(int(f.drops) for f in fds)
    assert pair_drops > 0
    assert pair_kept + pair_drops == Tl * kk * R
    # Sender-side counts describe exactly the kept items on the wire.
    assert sum(int(f.send_counts.sum()) for f in fds) == pair_kept

    rx = [jnp.stack([fds[s].send_x[d] for s in range(R)]) for d in range(R)]
    rc = [jnp.stack([fds[s].send_counts[d] for s in range(R)])
          for d in range(R)]
    bks = [fp.fused_bucket(rx[d], rc[d], num_slots=num_slots,
                           cap_slot=cap_slot) for d in range(R)]
    bucketed = sum(int(b[1].sum()) for b in bks)
    slot_drops = sum(int(b[3]) for b in bks)
    assert slot_drops > 0
    assert bucketed + slot_drops == pair_kept
    # The inverse map marks exactly the bucketed receive positions valid.
    assert sum(int(b[2].valid.sum()) for b in bks) == bucketed


def test_fused_layer_tight_caps_drops_counted():
    cfg = _cfg("none", cap_slot=4)
    params, x = _layer(cfg)
    y, _, stats = moe_layer_local(x, params, cfg, axis_name=None)
    assert int(stats.drops_slot) > 0
    assert np.isfinite(np.array(y)).all()


def test_fused_replicated_tight_caps_drops_counted():
    cfg = _cfg("none", dispatch_mode="replicated", cap_slot=4)
    params, x = _layer(cfg)
    y, _, stats = moe_layer_local(x, params, cfg, axis_name=None)
    assert int(stats.drops_slot) > 0
    assert np.isfinite(np.array(y)).all()


# ------------------------------------------------- engine helpers -------

def test_occurrence_by_histogram_matches_sort(rng):
    ids = jnp.asarray(rng.integers(0, 11, size=257), jnp.int32)
    occ_h = fp.occurrence_by_histogram(ids, 11)
    occ_s = occurrence_index(ids)
    assert np.array_equal(np.array(occ_h), np.array(occ_s))


# ----------------------------------------- real collectives (slow) ------

@pytest.mark.slow
def test_fused_a2a_shard_map_matches_reference():
    """Fused vs reference under real shard_map all_to_all on 4 CPU devices."""
    from tests.helpers import run_multidevice

    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.balancer import BalancerConfig
from repro.moe.gating import GatingConfig
from repro.moe.dispatch import moe_layer_reference
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local

R, E, kk, D, F, T = 4, 16, 4, 16, 24, 32 * 4
mesh = Mesh(np.array(jax.devices()[:R]).reshape(R), ("model",))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))
gcfg = GatingConfig(num_experts=E, top_k=kk)

cfg = MoEConfig(gating=gcfg,
                balancer=BalancerConfig(mode="ultraep", n_slot=2),
                d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk, cap_slot=T*kk)
ys = {}
for impl, layer in [("fused", moe_layer_local),
                    ("reference", moe_layer_reference)]:
    def run(x, router, w1, w3, w2):
        y, aux, stats = layer(
            x, MoEParams(router, w1, w3, w2), cfg, axis_name="model")
        return y, (stats.drops_dispatch + stats.drops_slot)[None]
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P("model", None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P("model", None), P("model")))
    y, drops = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0, impl
    ys[impl] = np.array(y)
np.testing.assert_allclose(ys["fused"], ys["reference"], rtol=1e-6,
                           atol=1e-6)
print("DONE")
""", n_devices=4)
    assert "DONE" in out
