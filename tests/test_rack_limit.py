"""Rack-limited routing: gate mask, bias co-design, verifier, e2e (S14).

Contracts:
  * every token's selected experts span at most ``rack_limit`` racks, for
    any config the gate accepts (hypothesis property);
  * ``rack_limit == num_racks`` is **bitwise** free routing -- ids, weights
    and counts -- so the masked path costs nothing when it does not bind;
  * the selection bias is behind ``stop_gradient``: perturbing it never
    changes combine-weight gradients, and the gradient *through* the bias
    is exactly zero;
  * ``rack_copy_volumes`` counts deduplicated (token, destination) payload
    copies, bounded by the per-tier item counts and, at M=1, by one
    inter-rack copy per token;
  * the two-level per-rack bias update steers rack load toward the global
    mean while staying bitwise the global update at ``num_racks == 1``;
  * ``verify_rack_limit`` flags corrupted selections and free-routing
    mismatches; the ``rack-limit`` lint rule confines top-k expert
    selection to the gate;
  * :meth:`Resilience.relay_schedule` builds replica broadcast trees from
    the LIVE health speeds (satellite of the same PR): scheduling with the
    real speeds never models slower than scheduling blind.
  * on a real factored (rack x lane) mesh, ``rack_limit == racks`` is
    bitwise the free hier_a2a layer, and ``rack_limit == 1`` runs
    drop-free with at most one at-gate inter-rack copy per token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import lint_source
from repro.analysis.plan_check import verify_rack_limit
from repro.moe.gating import (GatingConfig, gate, rack_copy_volumes,
                              update_router_bias)

from tests.helpers import run_multidevice

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _rand_gate(seed, T, d, E):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (T, d))
    w = jax.random.normal(k2, (d, E)) * d ** -0.5
    return x, w


def _check_span(racks, epg, M, k, seed):
    E = racks * epg
    cfg = GatingConfig(num_experts=E, top_k=k, num_racks=racks, rack_limit=M)
    x, w = _rand_gate(seed, 64, 8, E)
    out = gate(x, w, cfg)
    ids = np.asarray(out.expert_ids)
    spans = np.array([len(set(r.tolist())) for r in ids // epg])
    assert spans.max() <= M, (M, spans.max())
    assert verify_rack_limit(ids, rack_limit=M, num_racks=racks,
                             num_experts=E) == []


# ------------------------------------------------------- span property --

def test_span_never_exceeds_rack_limit(rng):
    """Deterministic sweep of the span<=M invariant over random configs."""
    for _ in range(30):
        racks = int(rng.choice([2, 4, 8]))
        epg = int(rng.choice([2, 4, 8]))
        M = int(rng.integers(1, racks + 1))
        k = int(rng.integers(1, min(8, M * epg) + 1))
        _check_span(racks, epg, M, k, int(rng.integers(0, 2 ** 16)))


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(racks=st.sampled_from([2, 4, 8]), epg=st.sampled_from([2, 4, 8]),
           data=st.data())
    def test_span_property_hypothesis(racks, epg, data):
        M = data.draw(st.integers(1, racks), label="rack_limit")
        k = data.draw(st.integers(1, min(8, M * epg)), label="top_k")
        seed = data.draw(st.integers(0, 2 ** 16), label="seed")
        _check_span(racks, epg, M, k, seed)


# ----------------------------------------------- M = racks: free bitwise --

@pytest.mark.parametrize("score_fn", ["softmax", "sigmoid"])
def test_limit_equal_racks_is_bitwise_free_routing(score_fn):
    E, k, G = 32, 6, 4
    x, w = _rand_gate(3, 128, 16, E)
    bias = jax.random.normal(jax.random.PRNGKey(9), (E,)) * 0.1
    kw = dict(num_experts=E, top_k=k, score_fn=score_fn, use_bias=True)
    free = gate(x, w, GatingConfig(**kw), bias=bias)
    masked = gate(x, w, GatingConfig(**kw, num_racks=G, rack_limit=G),
                  bias=bias)
    assert np.array_equal(np.asarray(free.expert_ids),
                          np.asarray(masked.expert_ids))
    assert np.array_equal(np.asarray(free.weights),
                          np.asarray(masked.weights))
    assert np.array_equal(np.asarray(free.counts), np.asarray(masked.counts))
    assert verify_rack_limit(masked.expert_ids, rack_limit=G, num_racks=G,
                             num_experts=E,
                             free_expert_ids=free.expert_ids) == []


# ------------------------------------------------- bias: selection only --

def test_bias_is_selection_only_no_gradient_leak():
    """stop_gradient contract: the bias can never leak into grads."""
    E, k, G = 16, 4, 4
    x, w = _rand_gate(5, 64, 8, E)
    cfg = GatingConfig(num_experts=E, top_k=k, use_bias=True,
                       num_racks=G, rack_limit=2)

    def weight_loss(bias):
        return gate(x, w, cfg, bias=bias).weights.sum()

    bias0 = jax.random.normal(jax.random.PRNGKey(0), (E,)) * 0.05
    g_bias = jax.grad(weight_loss)(bias0)
    assert np.array_equal(np.asarray(g_bias), np.zeros(E)), \
        "gradient flowed through the selection bias"

    # A bias perturbation too small to flip any selection must leave the
    # gradients w.r.t. activations and router weights bitwise unchanged.
    def xw_loss(x_, w_, bias):
        out = gate(x_, w_, cfg, bias=bias)
        return (out.weights ** 2).sum(), out.expert_ids

    (g_x, g_w), ids0 = jax.grad(xw_loss, argnums=(0, 1), has_aux=True)(
        x, w, bias0)
    (g_x2, g_w2), ids1 = jax.grad(xw_loss, argnums=(0, 1), has_aux=True)(
        x, w, bias0 + 1e-7)
    assert np.array_equal(np.asarray(ids0), np.asarray(ids1)), \
        "perturbation flipped selections; shrink it"
    assert np.array_equal(np.asarray(g_x), np.asarray(g_x2))
    assert np.array_equal(np.asarray(g_w), np.asarray(g_w2))


# ------------------------------------------------------ copy volumes ----

def test_rack_copy_volumes_hand_case():
    # R=4 ranks, rack_size=2 (racks {0,1} and {2,3}), E=8 (2 per rank).
    home = jnp.repeat(jnp.arange(4), 2)
    ids = jnp.asarray([
        [0, 1, 2, 3],   # experts on ranks 0,0,1,1: local=1 (rank0), intra=1
        [4, 5, 6, 7],   # ranks 2,2,3,3: two distinct racks? no -- one rack,
                        # two ranks, both inter from src rack 0: inter=1
        [0, 1, 0, 1],   # all on own rank: local=1
        [6, 7, 6, 7],   # all on rank 3: inter=1
    ], dtype=jnp.int32)
    tiers = np.asarray(rack_copy_volumes(ids, home, num_ranks=4, rack_size=2,
                                         src_rank=jnp.int32(0)))
    # token 0: rank0 (local) + rank1 (intra); token 1: rack1 once (inter);
    # token 2: local only; token 3: rack1 once (inter).
    assert tiers.tolist() == [2, 1, 2]


def test_rack_copy_volumes_m1_bounds_inter_by_tokens():
    E, k, G, R, lanes = 32, 8, 4, 8, 2
    home = jnp.repeat(jnp.arange(R), E // R)
    x, w = _rand_gate(11, 256, 16, E)
    out = gate(x, w, GatingConfig(num_experts=E, top_k=k,
                                  num_racks=G, rack_limit=1))
    tiers = np.asarray(rack_copy_volumes(out.expert_ids, home, num_ranks=R,
                                         rack_size=lanes,
                                         src_rank=jnp.int32(0)))
    T = out.expert_ids.shape[0]
    assert tiers[2] <= T                    # <= one inter-rack copy/token
    assert tiers.sum() <= T * k             # dedup never exceeds items
    free = gate(x, w, GatingConfig(num_experts=E, top_k=k))
    tiers_free = np.asarray(rack_copy_volumes(free.expert_ids, home,
                                              num_ranks=R, rack_size=lanes,
                                              src_rank=jnp.int32(0)))
    assert tiers[2] < tiers_free[2]         # the limit actually bound


# ------------------------------------------------- per-rack bias update --

def test_bias_update_num_racks1_is_bitwise_global():
    bias = jax.random.normal(jax.random.PRNGKey(1), (16,))
    counts = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 100)
    a = update_router_bias(bias, counts, 1e-3)
    b = update_router_bias(bias, counts, 1e-3, num_racks=1)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_bias_update_per_rack_two_level_semantics():
    E, G = 8, 2
    bias = jnp.zeros((E,))
    # Rack 0 overloaded (rack mean 30 vs global 20), rack 1 underloaded.
    counts = jnp.asarray([40, 20, 30, 30, 10, 10, 10, 10], jnp.int32)
    out = np.asarray(update_router_bias(bias, counts, 1.0, num_racks=G))
    # Within-rack (half gain): 40 above rack mean -> -0.5; 20 below -> +0.5;
    # the two at the mean -> 0.  Steering (full gain): rack 0 -> -1,
    # rack 1 -> +1; rack 1 experts all at their rack mean.
    assert out.tolist() == [-1.5, -0.5, -1.0, -1.0, 1.0, 1.0, 1.0, 1.0]
    # Uniform load: strict fixed point.
    flat = update_router_bias(bias, jnp.full((E,), 7, jnp.int32), 1.0,
                              num_racks=G)
    assert np.array_equal(np.asarray(flat), np.zeros(E))
    with pytest.raises(ValueError, match="multiple of num_racks"):
        update_router_bias(bias, counts, 1.0, num_racks=3)


# --------------------------------------------------- verifier and lint --

def test_verify_rack_limit_flags_corruption():
    E, k, G = 16, 4, 4
    x, w = _rand_gate(7, 64, 8, E)
    out = gate(x, w, GatingConfig(num_experts=E, top_k=k,
                                  num_racks=G, rack_limit=2))
    ids = np.asarray(out.expert_ids).copy()
    assert verify_rack_limit(ids, rack_limit=2, num_racks=G,
                             num_experts=E) == []
    ids[0] = [0, 4, 8, 12]                 # token 0 spans all four racks
    vio = verify_rack_limit(ids, rack_limit=2, num_racks=G, num_experts=E)
    assert [v.rule for v in vio] == ["rack-limit"]
    # Free-equality violation at a non-binding limit.
    free = gate(x, w, GatingConfig(num_experts=E, top_k=k))
    vio = verify_rack_limit(ids, rack_limit=G, num_racks=G, num_experts=E,
                            free_expert_ids=free.expert_ids)
    assert any("bitwise" in v.message for v in vio)
    # Vacuous when the limit is off.
    assert verify_rack_limit(ids, rack_limit=0, num_racks=G,
                             num_experts=E) == []
    assert verify_rack_limit(ids, rack_limit=2, num_racks=1,
                             num_experts=E) == []
    # Out-of-range ids are their own violation, not a crash.
    ids[0] = [0, 1, 2, E]
    vio = verify_rack_limit(ids, rack_limit=2, num_racks=G, num_experts=E)
    assert vio and "out of range" in vio[0].message


def test_lint_confines_top_k_to_the_gate():
    src = ("import jax\n"
           "def pick(scores):\n"
           "    _, ids = jax.lax.top_k(scores, 4)\n"
           "    return ids\n")
    vio = lint_source(src, "src/repro/moe/stages.py")
    assert any(v.rule == "rack-limit" for v in vio)
    # The gate itself is the sanctioned selection site.
    assert lint_source(src, "src/repro/moe/gating.py") == []
    # Outside moe/ the rule does not apply.
    assert not any(v.rule == "rack-limit"
                   for v in lint_source(src, "src/repro/core/planner.py"))
    # Per-line suppression works like every other rule.
    sup = src.replace("scores, 4)",
                      "scores, 4)  # uep-lint: disable=rack-limit")
    assert lint_source(sup, "src/repro/moe/stages.py") == []


# ------------------------------------- live-health relay (satellite) ----

def test_resilience_relay_schedule_uses_live_speeds():
    from repro.core import balancer
    from repro.core.comm_plan import simulate
    from repro.core.health import RankHealth
    from repro.moe.stages import Resilience

    R, E = 8, 16
    home = jnp.repeat(jnp.arange(R), E // R)
    # One hammered expert -> wide replica set -> relay trees matter.
    lam = np.ones((R, E), np.int64)
    lam[:, 0] = 400
    plan = balancer.solve(jnp.asarray(lam, jnp.int32), home,
                          balancer.BalancerConfig(mode="ultraep", n_slot=2))

    health = RankHealth(R)
    health.weight[:] = 1.0
    health.weight[1] = 0.05               # rank 1 is a deep straggler
    res = Resilience(health=health)
    assert np.array_equal(res.rank_speed(), health.planner_weights())

    aware = res.relay_schedule(plan, 1 << 20, home)
    blind = Resilience().relay_schedule(plan, 1 << 20, home)
    assert Resilience().rank_speed() is None
    speed = health.planner_weights()
    t_aware = simulate(aware, num_ranks=R, link_bandwidth=100e9,
                       rank_speed=speed)
    t_blind = simulate(blind, num_ranks=R, link_bandwidth=100e9,
                       rank_speed=speed)
    # Building the tree with the live speeds beats building it blind and
    # only then hitting the degraded fabric: relay duty routes around the
    # straggler, which ends up carrying strictly less planned volume.
    assert t_aware < t_blind
    assert aware.send_volume[1] < blind.send_volume[1]


# ------------------------------------------------ factored-mesh e2e -----

_RACK_LIMIT_SNIPPET = """
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
rack_mesh = Mesh(devs.reshape(RACKS, LANES), ("rack", "model"))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))

def run_case(gcfg):
    cfg = MoEConfig(gating=gcfg,
                    balancer=BalancerConfig(mode="ultraep", n_slot=2),
                    d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk,
                    cap_slot=T*kk, distribute_chunks=2,
                    dispatch_mode="hier_a2a", racks=RACKS)
    def run(x, router, w1, w3, w2):
        y, aux, stats = moe_layer_local(
            x, MoEParams(router, w1, w3, w2), cfg,
            axis_name=("rack", "model"))
        gt = (stats.gate_tier_tokens if stats.gate_tier_tokens is not None
              else -jnp.ones((3,), jnp.int32))
        return y, (stats.drops_dispatch + stats.drops_slot)[None], gt[None]
    f = jax.shard_map(run, mesh=rack_mesh, check_vma=False,
        in_specs=(P(("rack", "model"), None), P(None, None),
                  P(("rack", "model"), None, None),
                  P(("rack", "model"), None, None),
                  P(("rack", "model"), None, None)),
        out_specs=(P(("rack", "model"), None), P(("rack", "model")),
                   P(("rack", "model"), None)))
    y, drops, gt = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0
    return np.array(y), np.array(gt[0])

free = GatingConfig(num_experts=E, top_k=kk)
y_free, gt_free = run_case(free)
y_nonbind, gt_nonbind = run_case(GatingConfig(
    num_experts=E, top_k=kk, num_racks=RACKS, rack_limit=RACKS))
assert np.array_equal(y_free, y_nonbind), "rack_limit=racks != free routing"
assert np.array_equal(gt_free, gt_nonbind)
assert gt_free.sum() > 0 and (gt_free >= 0).all(), gt_free

y_m1, gt_m1 = run_case(GatingConfig(
    num_experts=E, top_k=kk, num_racks=RACKS, rack_limit=1))
assert np.isfinite(y_m1).all()
# M=1: at most one inter-rack payload copy per token, globally.
assert gt_m1[2] <= T, gt_m1
assert gt_m1[2] <= gt_free[2], (gt_m1, gt_free)
assert gt_m1.sum() <= T * kk
print("GATE-TIERS", gt_free.tolist(), gt_m1.tolist())
print("RACK-LIMIT-E2E-OK")
"""


def test_rack_limit_hier_2x4_e2e():
    """(2 racks x 4 lanes): non-binding limit is bitwise free; M=1 runs
    drop-free with bounded at-gate inter-rack copies in the layer stats."""
    out = run_multidevice(_RACK_LIMIT_SNIPPET)
    assert "RACK-LIMIT-E2E-OK" in out
