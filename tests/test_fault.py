"""Degraded-fabric resilience (DESIGN.md S13): health-weighted planning,
deterministic fault injection, and the graceful-degradation ladder.

The contracts under test:

* health model -- observed per-rank times become planner capacity weights;
  persistent stragglers quarantine and recover; degenerate states stay safe.
* health-weighted solve -- quota scales with weight, a quarantined rank
  drains to zero, and the plan passes the static verifier's health rules.
* ladder -- an injected solve failure degrades to the last-good plan
  (bitwise identical output to the unfailed run that solved the same plan),
  a second failure with a cold cache degrades to the no-balance plan, and
  no exception ever escapes the staged driver or the serving engine.
* payload screening -- injected NaN rows are dropped and counted, never
  reaching the residual stream.
* fallback-path lint -- silent swallow-all handlers in repro code are
  flagged; real handlers and suppressed lines are not.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import lint_source
from repro.analysis.violation import errors
from repro.analysis import plan_check
from repro.core import balancer
from repro.core.balancer import BalancerConfig
from repro.core.health import HealthConfig, RankHealth
from repro.core.topology import Topology
from repro.fault.injector import (FaultInjector, FaultSpec, PlannerFault,
                                  SolveTimeout, TransferFault)
from repro.moe.gating import GatingConfig
from repro.moe.layer import MoEConfig, init_moe_params, moe_layer_local
from repro.moe.stages import (Resilience, ResilienceConfig, run_staged_moe,
                              screen_payload)
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.train.fault import Supervisor, SupervisorConfig

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


# ------------------------------------------------------- health model ----


def test_health_weight_tracks_observed_speed():
    rh = RankHealth(4)
    for _ in range(12):
        rh.observe([1.0, 1.0, 2.0, 1.0])
    assert rh.weight[2] == pytest.approx(0.5, abs=0.05)
    assert rh.weight[[0, 1, 3]] == pytest.approx(1.0)


def test_health_quarantine_and_recovery():
    cfg = HealthConfig(quarantine_after=3, recover_after=4)
    rh = RankHealth(6, cfg)
    for _ in range(3):
        rh.observe([1.0, 1.0, 1.0, 1.0, 1.0, 50.0])
    assert rh.quarantined[5] and rh.num_quarantined == 1
    assert rh.planner_weights()[5] == 0.0
    for _ in range(4):
        rh.observe([1.0] * 6)
    assert not rh.quarantined[5]
    assert rh.planner_weights()[5] > 0.0


def test_health_ignores_lost_measurements():
    rh = RankHealth(4)
    for _ in range(5):
        rh.observe([1.0, np.nan, 1.0, -3.0])   # rank 1/3 measurements lost
    assert np.all(rh.weight > 0)
    assert not rh.quarantined.any()


def test_health_all_quarantined_degenerates_to_uniform():
    rh = RankHealth(3)
    for r in range(3):
        rh.quarantine(r)
    assert np.array_equal(rh.planner_weights(), np.ones(3))


def test_health_manual_quarantine_release():
    rh = RankHealth(4)
    rh.quarantine(1)
    assert rh.planner_weights()[1] == 0.0
    rh.release(1)
    assert rh.planner_weights()[1] == 1.0


# --------------------------------------------- health-weighted planning --


def _solve_weighted(w, R=4, Egrid=16, seed=0, rack_size=None):
    rng = np.random.default_rng(seed)
    lam = jnp.asarray(rng.integers(8, 64, size=(R, Egrid)), jnp.int32)
    home = jnp.asarray(np.repeat(np.arange(R), Egrid // R), jnp.int32)
    cfg = BalancerConfig(mode="ultraep", n_slot=2)
    plan = balancer.solve(lam, home, cfg, rack_size=rack_size,
                          health_weight=None if w is None
                          else jnp.asarray(w, jnp.float32))
    return plan, np.asarray(lam), np.asarray(home)


def test_half_speed_rank_gets_half_quota():
    w = np.array([0.5, 1.0, 1.0, 1.0])
    plan, lam, home = _solve_weighted(w)
    load = np.asarray(plan.u).sum(axis=0).astype(float)
    others = load[1:].mean()
    assert 0.3 * others <= load[0] <= 0.62 * others


def test_quarantined_rank_drains_to_zero_and_verifies():
    w = np.array([1.0, 1.0, 0.0, 1.0])
    plan, lam, home = _solve_weighted(w)
    assert int(np.asarray(plan.u)[:, 2].sum()) == 0
    assert int(np.asarray(plan.q)[:, :, 2].sum()) == 0
    vio = plan_check.verify_plan(plan, Topology.flat(4), lam=lam, home=home,
                                 rack_aware_mode=True, health_weight=w)
    assert errors(vio) == []


def test_rack_aware_quarantine_verifies():
    w = np.array([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 0.5, 0.0])
    plan, lam, home = _solve_weighted(w, R=8, Egrid=32, rack_size=4)
    assert int(np.asarray(plan.u)[:, 7].sum()) == 0
    topo = Topology(racks=2, ranks_per_rack=4)
    vio = plan_check.verify_plan(plan, topo, lam=lam, home=home,
                                 rack_aware_mode=True, health_weight=w)
    assert errors(vio) == []


def test_uniform_health_weight_matches_unweighted():
    """weight == ones must not change the solve (same caps, same search)."""
    p0, _, _ = _solve_weighted(None)
    p1, _, _ = _solve_weighted(np.ones(4))
    assert np.array_equal(np.asarray(p0.u), np.asarray(p1.u))
    assert np.array_equal(np.asarray(p0.q), np.asarray(p1.q))


# ------------------------------------------------------ fault injector ---


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor_strike")
    with pytest.raises(ValueError, match="severity"):
        FaultSpec("slow_rank", severity=1.5)
    with pytest.raises(ValueError, match="count"):
        FaultSpec("transfer_flaky", count=0)


def test_fault_windows_and_rank_speed():
    inj = FaultInjector([
        FaultSpec("slow_rank", rank=1, severity=0.5, start_step=2,
                  end_step=4)])
    inj.advance(1)
    assert np.array_equal(inj.rank_speed(4), np.ones(4))
    inj.advance(2)
    assert inj.rank_speed(4)[1] == 0.5
    inj.advance(4)
    assert np.array_equal(inj.rank_speed(4), np.ones(4))


def test_solve_faults_raise_in_window():
    inj = FaultInjector([FaultSpec("solve_fail", layer=3)])
    inj.check_solve(layer=2)               # other layer: no fault
    with pytest.raises(PlannerFault):
        inj.check_solve(layer=3)
    inj2 = FaultInjector([FaultSpec("solve_timeout")])
    with pytest.raises(SolveTimeout):
        inj2.check_solve()
    assert inj.fired["solve_fail"] == 1
    assert inj2.fired["solve_timeout"] == 1


def test_transfer_flaky_fails_then_clears():
    inj = FaultInjector([FaultSpec("transfer_flaky", count=2)])
    inj.advance(0)
    for _ in range(2):
        with pytest.raises(TransferFault) as ei:
            inj.check_transfer()
        assert ei.value.transient
    inj.check_transfer()                   # third attempt succeeds
    inj.advance(1)                         # next step: budget resets
    with pytest.raises(TransferFault):
        inj.check_transfer()


def test_corruption_is_deterministic_and_dtype_safe():
    inj = FaultInjector([FaultSpec("nan_payload", severity=0.25)], seed=7)
    inj.advance(3)
    x = jnp.ones((32, 8))
    a = np.asarray(inj.corrupt_payload(x, layer=0))
    b = np.asarray(inj.corrupt_payload(x, layer=0))
    assert np.array_equal(a, b, equal_nan=True)
    assert np.isnan(a).any(axis=1).sum() == 8      # ceil(0.25 * 32)
    ints = jnp.ones((32, 8), jnp.int8)
    assert inj.corrupt_payload(ints, layer=0) is ints


# ------------------------------------------------- payload screening -----


def test_screen_payload_drops_and_zeroes():
    xs = jnp.ones((8, 4))
    xs = xs.at[2].set(jnp.nan).at[5].set(jnp.inf)
    valid = jnp.asarray([True] * 6 + [False] * 2)
    out, v2, n = screen_payload(xs, valid)
    assert int(n) == 2
    assert np.isfinite(np.asarray(out)).all()
    assert not bool(v2[2]) and not bool(v2[5])
    assert bool(v2[0])


def test_screen_payload_passes_int_buffers():
    xs = jnp.ones((4, 4), jnp.int8)
    valid = jnp.ones(4, bool)
    out, v2, n = screen_payload(xs, valid)
    assert out is xs and int(n) == 0


# --------------------------------------------------- degradation ladder --


def test_resilience_noop_is_bit_identical(setup):
    cfg, params, x = setup
    y0, aux0, _ = moe_layer_local(x, params, cfg, axis_name=None)
    y1, aux1, s1 = moe_layer_local(x, params, cfg, axis_name=None,
                                   resilience=Resilience())
    assert np.array_equal(np.asarray(y0), np.asarray(y1))
    assert np.array_equal(np.asarray(aux0), np.asarray(aux1))
    assert int(s1.fallback_plans) == 0
    assert int(s1.dropped_payload_tokens) == 0


def test_solve_failure_reuses_last_good_bitwise(setup):
    """Step 0 solves clean (caching the plan); step 1's injected failure
    must reuse it -- and since the load is identical, the degraded step is
    bitwise identical to the unfailed run."""
    cfg, params, x = setup
    y_clean, _, _ = moe_layer_local(x, params, cfg, axis_name=None)
    inj = FaultInjector([FaultSpec("solve_fail", start_step=1)])
    res = Resilience(injector=inj)
    inj.advance(0)
    moe_layer_local(x, params, cfg, axis_name=None, resilience=res)
    assert res.last_good is not None
    inj.advance(1)
    y_deg, _, s = moe_layer_local(x, params, cfg, axis_name=None,
                                  resilience=res)
    assert int(s.fallback_plans) == 1
    assert res.counters["last_good_reuses"] == 1
    assert np.array_equal(np.asarray(y_clean), np.asarray(y_deg))


def test_double_failure_degrades_to_no_balance(setup):
    """No cached plan + solve failure -> the no-balance (home placement)
    plan: output stays finite, nothing escapes run_staged_moe."""
    cfg, params, x = setup
    inj = FaultInjector([FaultSpec("solve_fail")])
    res = Resilience(injector=inj)
    inj.advance(0)
    y, aux, s = run_staged_moe(x, params, cfg, axis_name=None,
                               resilience=res)
    assert int(s.fallback_plans) == 1
    assert res.counters["no_balance_fallbacks"] == 1
    assert np.isfinite(np.asarray(y)).all()


def test_nan_payload_dropped_counted_never_in_residual(setup):
    cfg, params, x = setup
    inj = FaultInjector([FaultSpec("nan_payload", severity=0.25)], seed=3)
    res = Resilience(injector=inj)
    inj.advance(0)
    y, aux, s = moe_layer_local(x, params, cfg, axis_name=None,
                                resilience=res)
    assert inj.fired["nan_payload"] > 0
    assert int(s.dropped_payload_tokens) > 0
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(aux)).all()


def test_transfer_flaky_survives_via_retry(setup):
    cfg, params, x = setup
    y0, _, _ = moe_layer_local(x, params, cfg, axis_name=None)
    inj = FaultInjector([FaultSpec("transfer_flaky", count=2)])
    res = Resilience(ResilienceConfig(max_transfer_retries=2), injector=inj)
    inj.advance(0)
    y1, _, s = moe_layer_local(x, params, cfg, axis_name=None,
                               resilience=res)
    assert res.counters["transfer_retries"] == 2
    assert res.counters["transfer_fallbacks"] == 0
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


def test_transfer_exhaustion_downgrades_not_raises(setup):
    cfg, params, x = setup
    inj = FaultInjector([FaultSpec("transfer_flaky", count=5)])
    res = Resilience(ResilienceConfig(max_transfer_retries=1), injector=inj)
    inj.advance(0)
    y, _, s = moe_layer_local(x, params, cfg, axis_name=None,
                              resilience=res)
    assert res.counters["transfer_fallbacks"] == 1
    assert int(s.fallback_plans) >= 1
    assert np.isfinite(np.asarray(y)).all()


def test_solve_deadline_trips_ladder(setup):
    cfg, params, x = setup
    res = Resilience(ResilienceConfig(solve_deadline_s=0.0))
    y, _, s = run_staged_moe(x, params, cfg, axis_name=None, resilience=res)
    assert int(s.fallback_plans) == 1
    assert np.isfinite(np.asarray(y)).all()


def test_quarantined_ranks_stat_reported(setup):
    cfg, params, x = setup
    rh = RankHealth(1)
    res = Resilience(health=rh)
    _, _, s = run_staged_moe(x, params, cfg, axis_name=None, resilience=res)
    assert int(s.quarantined_ranks) == 0


# ----------------------------------------------------- train supervisor --


def _run_supervisor(tmp_path, rank_times, steps=8, num_ranks=4):
    scfg = SupervisorConfig(checkpoint_dir=str(tmp_path),
                            checkpoint_every=100, num_ranks=num_ranks)

    def step_fn(state, batch):
        return state, {"loss": jnp.asarray(0.0),
                       "rank_step_times": np.asarray(rank_times)}

    sup = Supervisor(scfg, step_fn, lambda step: step)
    state = {"w": jnp.zeros(2)}
    sup.run(state, 0, steps)
    return sup


def test_supervisor_feeds_rank_health(tmp_path):
    sup = _run_supervisor(tmp_path, [1.0, 1.0, 4.0, 1.0])
    rh = sup.rank_health()
    assert rh.weight[2] == pytest.approx(0.25, abs=0.05)
    assert rh.weight[0] == pytest.approx(1.0)
    # the planner-facing vector is consumable as a health_weight
    plan, lam, home = _solve_weighted(rh.planner_weights())
    load = np.asarray(plan.u).sum(axis=0).astype(float)
    assert load[2] < 0.5 * load[[0, 1, 3]].mean()


def test_supervisor_broadcasts_global_time_without_metrics(tmp_path):
    scfg = SupervisorConfig(checkpoint_dir=str(tmp_path),
                            checkpoint_every=100, num_ranks=3)
    sup = Supervisor(scfg, lambda s, b: (s, {"loss": jnp.asarray(0.0)}),
                     lambda step: step)
    sup.run({"w": jnp.zeros(2)}, 0, 4)
    rh = sup.rank_health()
    assert rh._seen == 4
    assert np.allclose(rh.weight, 1.0)     # uniform broadcast -> no skew


# -------------------------------------------------------- serving engine --


def _engine(prefill_fails=0, decode_fails=0, nan_logits=False,
            max_retries=1, error=RuntimeError):
    V = 11
    calls = {"prefill": 0, "decode": 0}

    def prefill(toks, cache, pos, length):
        calls["prefill"] += 1
        if calls["prefill"] <= prefill_fails:
            raise error("injected prefill fault")
        logits = jnp.full((1, toks.shape[1], V),
                          jnp.nan if nan_logits else 0.0)
        if not nan_logits:
            logits = logits.at[..., 3].set(1.0)
        return logits, cache

    def decode(toks, caches):
        calls["decode"] += 1
        if calls["decode"] <= decode_fails:
            raise error("injected decode fault")
        B = toks.shape[0]
        logits = jnp.zeros((B, 1, V)).at[..., 5].set(1.0)
        return logits, caches

    eng = ServingEngine(
        EngineConfig(chunk_size=8, decode_batch=2, max_retries=max_retries),
        prefill_fn=prefill, decode_fn=decode,
        new_cache_fn=lambda b: {"n": jnp.zeros((b, 1))},
        stack_caches=lambda cs: {"n": jnp.concatenate(
            [c["n"] for c in cs])})
    return eng, calls


def _submit(eng, n=2):
    for i in range(n):
        eng.submit(Request(rid=i, prompt=np.arange(10, dtype=np.int32),
                           max_new_tokens=3))


def test_engine_retries_transient_prefill_fault():
    eng, calls = _engine(prefill_fails=1)
    _submit(eng, n=2)
    done = eng.run()
    assert len(done) == 2 and not any(r.failed for r in done)
    assert eng.fault_counters["prefill_retries"] == 1
    assert eng.fault_counters["failed_requests"] == 0


def test_engine_retires_permanently_failing_prefill():
    eng, _ = _engine(prefill_fails=10 ** 6)
    _submit(eng, n=2)
    done = eng.run()                       # must terminate, not raise
    assert len(done) == 2 and all(r.failed for r in done)
    assert eng.fault_counters["failed_requests"] == 2
    assert eng.ttft().size == 0 and eng.tpot().size == 0


def test_engine_retires_failing_decode_group():
    eng, _ = _engine(decode_fails=10 ** 6)
    _submit(eng, n=2)
    done = eng.run()
    assert len(done) == 2 and all(r.failed for r in done)
    # max_retries=1: one retry before the group is retired
    assert eng.fault_counters["decode_retries"] == 1
    assert eng.fault_counters["failed_requests"] == 2


def test_engine_screens_nonfinite_logits():
    eng, _ = _engine(nan_logits=True)
    _submit(eng, n=1)
    done = eng.run()
    assert not done[0].failed
    assert done[0].output[0] == 0          # all-NaN row degrades to token 0
    assert eng.fault_counters["nonfinite_logits"] >= 1


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_engine_device_error_escapes_injected_fault_retires(phase):
    """A device error (OOM, lost chip) is not transient: it escapes run()
    with nothing retried or retired; an injected fault is still retried,
    then retired."""
    fails = {f"{phase}_fails": 10 ** 6}
    eng, calls = _engine(error=jax.errors.JaxRuntimeError, **fails)
    _submit(eng, n=2)
    with pytest.raises(jax.errors.JaxRuntimeError):
        eng.run()
    assert calls[phase] == 1
    assert not any(eng.fault_counters.values())

    eng, calls = _engine(**fails)
    _submit(eng, n=2)
    done = eng.run()
    assert len(done) == 2 and all(r.failed for r in done)
    assert eng.fault_counters[f"{phase}_retries"] >= 1
    assert eng.fault_counters["failed_requests"] == 2


# ----------------------------------------------------- fallback-path lint --


def test_lint_flags_bare_except_in_repro():
    vio = lint_source("try:\n    x = 1\nexcept:\n    pass\n",
                      "src/repro/foo.py")
    assert [v.rule for v in vio] == ["fallback-path"]


def test_lint_flags_swallow_all_pass():
    vio = lint_source("try:\n    x = 1\nexcept Exception:\n    pass\n",
                      "src/repro/foo.py")
    assert [v.rule for v in vio] == ["fallback-path"]


def test_lint_allows_handlers_with_real_bodies():
    src = "try:\n    x = 1\nexcept Exception as e:\n    n = 1\n"
    assert lint_source(src, "src/repro/foo.py") == []


def test_lint_fallback_suppression_and_scope():
    sup = ("try:\n    x = 1\n"
           "except Exception:  # uep-lint: disable=fallback-path\n"
           "    pass\n")
    assert lint_source(sup, "src/repro/foo.py") == []
    bare = "try:\n    x = 1\nexcept:\n    pass\n"
    assert lint_source(bare, "tools/foo.py") == []   # tools are out of scope
