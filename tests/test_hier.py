"""Hierarchical two-level EP: rack-aware planning + two-hop dispatch.

Contracts (DESIGN.md S9):
  * ``hier_a2a`` on a factored (rack, lane) mesh is **bit-identical** to the
    flat fused ``a2a`` path at zero-drop capacities -- the two-hop wire is a
    pure relabelling of the flat all_to_all, replica weights are exact copies
    so plan differences cannot change outputs, and the grouped FFN is
    row-independent.
  * Rack-aware solves never carry more inter-rack token volume than the flat
    solve of the same load matrix (the rack-local reroute tier achieves the
    per-expert intra-rack matching bound).
  * Tiered relay schedules place every stage-two edge intra-rack by
    construction, with at most one inter-rack transfer per (expert, rack).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner as pl
from repro.core.comm_plan import build_relay_schedule, simulate
from repro.core.topology import Topology
from tests.helpers import run_multidevice

# ------------------------------------------------ planner: rack-aware ----


def _random_case(rng, R=8, epr=4, scale=30.0, alpha=1.3):
    E = R * epr
    lam = (rng.pareto(alpha, size=(R, E)) * scale).astype(np.int64)
    home = np.repeat(np.arange(R), epr)
    return jnp.array(lam), jnp.array(home)


@pytest.mark.parametrize("rack_size", [2, 4])
def test_rack_solve_never_more_inter_rack_volume(rack_size):
    """Property (fixed seeds): rack-aware inter-rack token volume <= flat."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        lam, home = _random_case(rng, R=8, epr=int(rng.choice([2, 4])),
                                 alpha=float(rng.choice([1.1, 1.3, 2.0])))
        flat = pl.solve_plan(lam, home, n_slot=2, u_min=4)
        rack = pl.solve_plan(lam, home, n_slot=2, u_min=4,
                             rack_size=rack_size)
        # Validity invariants survive the rack-aware tie-break + reroute.
        lam_e = np.array(lam.sum(axis=0))
        assert np.array_equal(np.array(rack.u.sum(axis=1)), lam_e)
        assert np.array_equal(np.array(rack.q.sum(axis=2)), np.array(lam))
        assert np.array_equal(np.array(rack.q.sum(axis=0)), np.array(rack.u))
        # Tier accounting conserves items and is exported on the plan.
        vol_rack = np.array(rack.tier_tokens)
        vol_flat = np.array(pl.token_tier_volumes(flat.q, rack_size))
        assert vol_rack.sum() == lam_e.sum() == vol_flat.sum()
        assert vol_rack[2] <= vol_flat[2], (trial, vol_rack, vol_flat)
        assert flat.tier_tokens is None


def test_rack_reroute_same_quota_is_intra_optimal(rng):
    """For a fixed quota table, the rack tier hits the per-expert intra-rack
    matching bound sum_g min(demand_g, quota_g) exactly."""
    L = 4
    for _ in range(10):
        lam, home = _random_case(rng)
        u, _tau = pl.solve_replication(lam, home, n_slot=2, u_min=4)
        q = pl.solve_reroute(lam, u, rack_size=L)
        assert np.array_equal(np.array(q.sum(axis=2)), np.array(lam))
        assert np.array_equal(np.array(q.sum(axis=0)), np.array(u))
        R, E = lam.shape
        d = np.array(lam.T).reshape(E, R // L, L).sum(axis=2)   # (E, G)
        s = np.array(u).reshape(E, R // L, L).sum(axis=2)
        bound = np.minimum(d, s).sum()
        same_rack = (np.arange(R)[:, None] // L) == (np.arange(R)[None, :] // L)
        intra = np.array(q).sum(axis=1)[same_rack].sum()
        assert intra == bound


def test_rack_size_one_rack_is_flat_bitwise(rng):
    """G=1 degenerates to the flat solve bit-for-bit (plan-level compat)."""
    lam, home = _random_case(rng)
    R = lam.shape[0]
    flat = pl.solve_plan(lam, home, n_slot=2, u_min=4)
    one = pl.solve_plan(lam, home, n_slot=2, u_min=4, rack_size=R)
    assert np.array_equal(np.array(flat.u), np.array(one.u))
    assert np.array_equal(np.array(flat.q), np.array(one.q))
    assert np.array_equal(np.array(flat.x), np.array(one.x))
    assert int(flat.tau) == int(one.tau)


def test_tier_volume_accounting(rng):
    lam, home = _random_case(rng)
    p = pl.solve_plan(lam, home, n_slot=2, u_min=4, rack_size=4)
    vols = np.array(p.tier_tokens)
    # Local = the diagonal of the pair matrix; everything sums to all items.
    per_pair = np.array(p.q).sum(axis=1)
    assert vols[0] == np.trace(per_pair)
    assert vols.sum() == per_pair.sum()
    reps = np.array(p.tier_replicas)
    is_rep = (np.array(p.u).T > 0) & (
        np.array(home)[None, :] != np.arange(8)[:, None])
    assert reps.sum() == is_rep.sum()


# -------------------------------------------- comm plan: tiered relays ---


def _hosted_case(rng, R=16, epr=2, n_slot=2):
    E = R * epr
    lam = (rng.pareto(1.1, size=(R, E)) * 40).astype(np.int64)
    home = np.repeat(np.arange(R), epr)
    p = pl.solve_plan(jnp.array(lam), jnp.array(home), n_slot=n_slot, u_min=8,
                      rack_size=4)
    hosted = np.array(p.u > 0)                # (E, R)
    hosted[np.arange(E), home] = True
    return hosted, home


def test_tiered_relay_lands_intra_rack(rng):
    topo = Topology(racks=4, ranks_per_rack=4)
    hosted, home = _hosted_case(rng)
    sched = build_relay_schedule(hosted, home, 1 << 20, topology=topo)
    inter_inbound = {}   # (expert, rack) -> [relay rank]
    for e in sched.edges:
        if not topo.same_rack(e.src, e.dst):
            inter_inbound.setdefault(
                (e.expert, topo.rack_of(e.dst)), []).append(e.dst)
    # Exactly one inter-rack copy per (expert, remote rack): minimal
    # scale-out volume.
    assert all(len(v) == 1 for v in inter_inbound.values())
    # Every sender already holds the expert (home, or fed by an earlier
    # edge): the schedule is a valid broadcast forest, and remote-rack
    # fan-out beyond the single relay copy stays intra-rack.
    holders = {}
    for e in sched.edges:
        assert e.src == int(home[e.expert]) or \
            e.src in holders.get(e.expert, ()), (e.src, e.expert)
        holders.setdefault(e.expert, set()).add(e.dst)
    # Every hosted replica still receives its weights exactly once.
    recv = {}
    for e in sched.edges:
        recv[(e.expert, e.dst)] = recv.get((e.expert, e.dst), 0) + 1
    E, R = hosted.shape
    for ee in range(E):
        for r in range(R):
            want = 1 if (hosted[ee, r] and r != home[ee]) else 0
            assert recv.get((ee, r), 0) == want, (ee, r)


def test_simulate_tiered_stats(rng):
    topo = Topology(racks=4, ranks_per_rack=4, inter_beta=12.5e9)
    hosted, home = _hosted_case(rng)
    sched = build_relay_schedule(hosted, home, 8 << 20, topology=topo)
    t, stats = simulate(sched, num_ranks=16, link_bandwidth=100e9,
                        topology=topo, return_stats=True)
    assert t > 0 and np.isfinite(t)
    assert stats.edge_finish.shape == (len(sched.edges),)
    assert (stats.edge_finish > 0).all()
    assert abs(t - stats.edge_finish.max()) < 1e-12
    total = sum(e.nbytes for e in sched.edges)
    assert stats.intra_bytes + stats.inter_bytes == total
    # The same schedule on a flat fabric (no topology) still simulates.
    t_flat = simulate(sched, num_ranks=16, link_bandwidth=100e9)
    assert isinstance(t_flat, float) and t_flat > 0


def test_flat_relay_schedule_unchanged(rng):
    """topology=None reproduces the original threshold-based relay builder."""
    hosted, home = _hosted_case(rng)
    sched = build_relay_schedule(hosted, home, 1 << 20, relay_threshold=3)
    assert all(e.stage in (0, 1) for e in sched.edges)
    assert sched.max_send_volume > 0


# ------------------------------------------ layer: single-rank bitcompat --


def test_hier_single_rank_equals_flat_fused():
    from repro.core.balancer import BalancerConfig
    from repro.moe.gating import GatingConfig
    from repro.moe.layer import MoEConfig, init_moe_params, moe_layer_local

    E, D, F, T = 8, 16, 32, 64

    def cfg(mode):
        return MoEConfig(
            gating=GatingConfig(num_experts=E, top_k=2),
            balancer=BalancerConfig(mode="ultraep", n_slot=2),
            d_model=D, d_ff=F, ep_size=1, cap_pair=T * 2, cap_slot=T * 2,
            dispatch_mode=mode)

    params = init_moe_params(jax.random.PRNGKey(0), cfg("a2a"))
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    y_flat, _, _ = moe_layer_local(x, params, cfg("a2a"), axis_name=None)
    y_hier, _, _ = moe_layer_local(x, params, cfg("hier_a2a"), axis_name=None)
    assert np.array_equal(np.array(y_flat), np.array(y_hier))


def test_config_validation_at_construction():
    from repro.core.balancer import BalancerConfig
    from repro.moe.gating import GatingConfig
    from repro.moe.layer import MoEConfig

    def mk(**kw):
        base = dict(gating=GatingConfig(num_experts=8, top_k=2),
                    balancer=BalancerConfig(mode="ultraep", n_slot=2),
                    d_model=8, d_ff=8, ep_size=4, cap_pair=8, cap_slot=8)
        base.update(kw)
        return MoEConfig(**base)

    with pytest.raises(ValueError, match="dispatch_mode"):
        mk(dispatch_mode="bogus")
    with pytest.raises(ValueError, match="racks"):
        mk(racks=3)
    assert mk(dispatch_mode="hier_a2a", racks=2).rack_size == 2
    assert mk(racks=1).rack_size is None


# --------------------------------- real collectives: factored 2x4 mesh ---

_HIER_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.balancer import BalancerConfig
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local

RACKS, LANES = %(racks)d, %(lanes)d
R = RACKS * LANES
E, kk, D, F = 2 * R, 4, 16, 24
T = 32 * R
devs = np.array(jax.devices()[:R])
flat_mesh = Mesh(devs.reshape(R), ("model",))
rack_mesh = Mesh(devs.reshape(RACKS, LANES), ("rack", "model"))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))
gcfg = GatingConfig(num_experts=E, top_k=kk)

def run_case(mesh, mode, racks, axis_name, ep_spec):
    cfg = MoEConfig(gating=gcfg,
                    balancer=BalancerConfig(mode="ultraep", n_slot=2),
                    d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk,
                    cap_slot=T*kk, distribute_chunks=2, dispatch_mode=mode,
                    racks=racks)
    def run(x, router, w1, w3, w2):
        y, aux, stats = moe_layer_local(
            x, MoEParams(router, w1, w3, w2), cfg, axis_name=axis_name)
        tiers = (stats.tier_tokens if stats.tier_tokens is not None
                 else jnp.zeros((3,), jnp.int32))
        return y, (stats.drops_dispatch + stats.drops_slot)[None], \\
               tiers[None]
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P(ep_spec, None), P(None, None), P(ep_spec, None, None),
                  P(ep_spec, None, None), P(ep_spec, None, None)),
        out_specs=(P(ep_spec, None), P(ep_spec), P(ep_spec, None)))
    y, drops, tiers = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0, mode
    return np.array(y), np.array(tiers[0])

y_flat, _ = run_case(flat_mesh, "a2a", 1, "model", "model")
y_hier, tiers = run_case(rack_mesh, "hier_a2a", RACKS, ("rack", "model"),
                         ("rack", "model"))
assert np.array_equal(y_flat, y_hier), (
    np.abs(y_flat - y_hier).max(), "hier_a2a != flat a2a")
if RACKS > 1:
    assert tiers.sum() == T * kk, tiers   # every item accounted to a tier
    print("TIERS", tiers.tolist())
print("HIER-BITWISE-OK")
"""


def test_hier_2x4_bitwise_equals_flat():
    """(2 racks x 4 lanes) factored mesh == flat 8-rank mesh, bit for bit."""
    out = run_multidevice(_HIER_SNIPPET % dict(racks=2, lanes=4))
    assert "HIER-BITWISE-OK" in out


def test_hier_1rack_topology_bitwise_equals_flat():
    """1-rack factored mesh (1x4): the degenerate topology acceptance case."""
    out = run_multidevice(_HIER_SNIPPET % dict(racks=1, lanes=4),
                          n_devices=4)
    assert "HIER-BITWISE-OK" in out


def test_hier_full_model_init_on_rack_mesh():
    """Full-LM parameter init + sharding specs on a factored (1, 2, 4) mesh:
    the single-group init view must collapse the rack factoring (regression:
    dataclasses.replace(mcfg, ep_size=1) used to trip the racks validation),
    and every param spec must accept the (rack, model) axis tuple."""
    out = run_multidevice("""
import jax, numpy as np
from repro.launch.mesh import make_rack_mesh, pctx_for_mesh
from repro.configs import get_config
from repro.models.model import init_lm
from repro.models.transformer import RuntimeConfig, moe_config
from repro.core.balancer import BalancerConfig
from repro.parallel.sharding import lm_param_specs

mesh = make_rack_mesh(1, 2, 4)
pctx = pctx_for_mesh(mesh)
assert pctx.ep_size == 8 and pctx.racks == 2
assert pctx.ep_axes == ("rack", "model")
cfg = get_config("tiny-moe")
rcfg = RuntimeConfig(balancer=BalancerConfig(mode="ultraep", n_slot=2),
                     cf_pair=8, cf_slot=8)
mcfg = moe_config(cfg, rcfg, pctx, tokens_per_rank=8)
assert mcfg.dispatch_mode == "hier_a2a" and mcfg.racks == 2
params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
specs = lm_param_specs(cfg, rcfg, pctx)
leaves = jax.tree.leaves(params)
assert all(np.isfinite(np.asarray(l)).all() for l in leaves
           if hasattr(l, 'dtype') and np.issubdtype(l.dtype, np.floating))
print("RACK-INIT-OK", len(leaves))
""")
    assert "RACK-INIT-OK" in out


@pytest.mark.slow
def test_hier_full_model_train_step_on_rack_mesh():
    """(1 data, 2 rack, 4 model) mesh: full LM train step with hier dispatch,
    loss finite and decreasing (multi-layer integration)."""
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_rack_mesh, pctx_for_mesh
from repro.configs import get_config
from repro.models.model import init_lm
from repro.models.transformer import RuntimeConfig
from repro.core.balancer import BalancerConfig
from repro.train.loop import TrainConfig, init_train_state, make_train_step
from repro.optim import adamw

mesh = make_rack_mesh(1, 2, 4)
pctx = pctx_for_mesh(mesh)
assert pctx.ep_size == 8 and pctx.racks == 2
cfg = get_config("tiny-moe")
rcfg = RuntimeConfig(balancer=BalancerConfig(mode="ultraep", n_slot=2),
                     cf_pair=8, cf_slot=8)
params = init_lm(jax.random.PRNGKey(0), cfg, rcfg, pctx)
opt = adamw(1e-3)
state = init_train_state(params, opt, cfg)
step = jax.jit(make_train_step(cfg, rcfg, pctx, opt, TrainConfig()),
               donate_argnums=(0,))
B, S = 8, 32
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                      cfg.vocab_size),
         "targets": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                       cfg.vocab_size)}
losses = []
for _ in range(5):
    state, m = step(state, batch)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0] and np.isfinite(losses[-1]), losses
print("RACK-MESH-TRAIN-OK", losses[0], losses[-1])
""")
    assert "RACK-MESH-TRAIN-OK" in out


# ------------------------------ in-process factored mesh (8 devices) -----

requires8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@requires8
def test_hier_replicated_mode_on_rack_mesh_inprocess():
    """Replicated (decode) dispatch on a factored mesh: two-stage replica
    streaming + tiered psum matches the flat-mesh result."""
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.core.balancer import BalancerConfig
    from repro.moe.gating import GatingConfig
    from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local

    RACKS, LANES = 2, 4
    R = RACKS * LANES
    E, kk, D, F, T = 16, 2, 8, 12, 32
    devs = np.array(jax.devices()[:R])
    pk = jax.random.split(jax.random.PRNGKey(0), 5)
    router = jax.random.normal(pk[0], (D, E), jnp.float32) * D ** -0.5
    w1 = jax.random.normal(pk[1], (E, D, F)) * D ** -0.5
    w3 = jax.random.normal(pk[2], (E, D, F)) * D ** -0.5
    w2 = jax.random.normal(pk[3], (E, F, D)) * F ** -0.5
    x = jax.random.normal(pk[4], (T, D))
    gcfg = GatingConfig(num_experts=E, top_k=kk)

    def run_case(mesh, racks, axis_name, ep_spec):
        cfg = MoEConfig(gating=gcfg,
                        balancer=BalancerConfig(mode="ultraep", n_slot=2),
                        d_model=D, d_ff=F, ep_size=R, cap_pair=T * kk,
                        cap_slot=T * kk, dispatch_mode="replicated",
                        racks=racks)

        def run(x, router, w1, w3, w2):
            y, _, stats = moe_layer_local(
                x, MoEParams(router, w1, w3, w2), cfg, axis_name=axis_name)
            return y, stats.drops_slot[None]

        f = jax.shard_map(
            run, mesh=mesh, check_vma=False,
            in_specs=(P(None, None), P(None, None), P(ep_spec, None, None),
                      P(ep_spec, None, None), P(ep_spec, None, None)),
            out_specs=(P(None, None), P(ep_spec)))
        y, drops = jax.jit(f)(x, router, w1, w3, w2)
        assert int(drops.sum()) == 0
        return np.array(y)

    y_flat = run_case(Mesh(devs.reshape(R), ("model",)), 1, "model", "model")
    y_rack = run_case(Mesh(devs.reshape(RACKS, LANES), ("rack", "model")),
                      RACKS, ("rack", "model"), ("rack", "model"))
    np.testing.assert_allclose(y_rack, y_flat, rtol=1e-6, atol=1e-6)
