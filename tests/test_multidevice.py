"""Multi-device EP semantics (subprocess, 8 virtual CPU devices).

These run the REAL shard_map data path with real collectives: exactness vs
the per-token oracle, gradient equivalence (the paper's S4.2 training-
equivalence claim), replicated-dispatch decode mode, and the pod-axis
pipeline.
"""

import pytest

from tests.helpers import run_multidevice

pytestmark = pytest.mark.slow


def test_ep8_all_modes_match_oracle():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local
from repro.moe.gating import GatingConfig, gate
from repro.core.balancer import BalancerConfig
from repro.moe.reference import moe_ref

R, E, kk, D, F, T = 8, 32, 4, 16, 24, 32 * 8
mesh = Mesh(np.array(jax.devices()).reshape(R), ("model",))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))
gcfg = GatingConfig(num_experts=E, top_k=kk)
go = gate(x, router, gcfg)
y_ref = moe_ref(x, go.expert_ids, go.weights, w1, w3, w2)

for mode in ["none", "ultraep", "eplb_plus"]:
    cfg = MoEConfig(gating=gcfg, balancer=BalancerConfig(mode=mode, n_slot=2),
                    d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk,
                    cap_slot=T*kk, distribute_chunks=2)
    def run(x, router, w1, w3, w2):
        y, aux, stats = moe_layer_local(
            x, MoEParams(router, w1, w3, w2), cfg, axis_name="model")
        return y, (stats.drops_dispatch + stats.drops_slot)[None], \
               stats.post_max[None]
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P("model", None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P("model", None), P("model"), P("model")))
    y, drops, post = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0, mode
    np.testing.assert_allclose(np.array(y), np.array(y_ref),
                               rtol=2e-4, atol=2e-4)
    print(mode, "OK", int(post[0]))
print("DONE")
""")
    assert "DONE" in out


def test_ep8_gradient_equivalence():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.moe.layer import MoEConfig, MoEParams, moe_layer_local
from repro.moe.gating import GatingConfig, gate
from repro.core.balancer import BalancerConfig
from repro.moe.reference import moe_ref

R, E, kk, D, F, T = 8, 32, 4, 16, 24, 32 * 8
mesh = Mesh(np.array(jax.devices()).reshape(R), ("model",))
pk = jax.random.split(jax.random.PRNGKey(0), 5)
router = jax.random.normal(pk[0], (D, E), jnp.float32) * D**-0.5
w1 = jax.random.normal(pk[1], (E, D, F)) * D**-0.5
w3 = jax.random.normal(pk[2], (E, D, F)) * D**-0.5
w2 = jax.random.normal(pk[3], (E, F, D)) * F**-0.5
x = jax.random.normal(pk[4], (T, D))
gcfg = GatingConfig(num_experts=E, top_k=kk)
cfg = MoEConfig(gating=gcfg, balancer=BalancerConfig(mode="ultraep", n_slot=2),
                d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk, cap_slot=T*kk)
def loss_ep(w1, w3, w2):
    def run(x, router, w1, w3, w2):
        y, aux, _ = moe_layer_local(x, MoEParams(router, w1, w3, w2), cfg,
                                    axis_name="model")
        return y
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P("model", None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P("model", None))
    return (f(x, router, w1, w3, w2) ** 2).sum()
def loss_ref(w1, w3, w2):
    go = gate(x, router, gcfg)
    return (moe_ref(x, go.expert_ids, go.weights, w1, w3, w2) ** 2).sum()
g_ep = jax.jit(jax.grad(loss_ep, argnums=(0, 1, 2)))(w1, w3, w2)
g_rf = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(w1, w3, w2)
for a, b in zip(g_ep, g_rf):
    np.testing.assert_allclose(np.array(a), np.array(b), rtol=5e-4,
                               atol=5e-4)
print("GRADS-EQUIV")
""")
    assert "GRADS-EQUIV" in out


def test_pipeline_pod_axis():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.pipeline import pipeline_apply
n, M, B, D, L = 4, 6, 2, 8, 8
mesh = Mesh(np.array(jax.devices()[:n]), ("pod",))
w = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))
def stage_fn(x, ws):
    for i in range(ws.shape[0]):
        x = jnp.tanh(x @ ws[i])
    return x
f = jax.shard_map(lambda x, w: pipeline_apply(x, w, stage_fn, axis_name="pod",
                                              num_stages=n),
                  mesh=mesh, check_vma=False,
                  in_specs=(P(None, None, None), P("pod", None, None)),
                  out_specs=P(None, None, None))
out = jax.jit(f)(x, w)
ref = x
for i in range(L):
    ref = jnp.tanh(ref @ w[i])
np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-5,
                           atol=1e-5)
print("PIPELINE-OK")
""")
    assert "PIPELINE-OK" in out


def test_grad_compression_psum():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.optim.grad_compress import CompressState, psum_compressed
n = 4
mesh = Mesh(np.array(jax.devices()[:n]), ("pod",))
g = jax.random.normal(jax.random.PRNGKey(0), (n, 64, 64))
def run(g):
    st = CompressState(jnp.zeros_like(g[0]))
    out, st = psum_compressed(g[0], st, "pod")
    return out[None], st.residual[None]
f = jax.shard_map(run, mesh=mesh, check_vma=False,
                  in_specs=(P("pod", None, None),),
                  out_specs=(P("pod", None, None), P("pod", None, None)))
out, res = jax.jit(f)(g)
exact = g.mean(axis=0)
err = np.abs(np.array(out[0]) - np.array(exact)).max()
scale = np.abs(np.array(g)).max() / 127
assert err < 2 * scale, (err, scale)  # quantization-level error only
print("COMPRESS-OK", float(err))
""")
    assert "COMPRESS-OK" in out


def test_full_model_train_step_on_mesh():
    """2x4 mesh: full LM train step with UltraEP, loss finite + decreasing."""
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh, pctx_for_mesh
from repro.configs import get_config
import dataclasses
from repro.models.model import init_lm
from repro.models.transformer import RuntimeConfig
from repro.core.balancer import BalancerConfig
from repro.parallel.sharding import lm_param_specs, batch_specs, opt_state_specs
from repro.train.loop import TrainConfig, TrainState, init_train_state, make_train_step
from repro.optim import adamw
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = make_test_mesh(2, 4)
pctx = pctx_for_mesh(mesh)
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
print("MESH-TRAIN-OK", losses[0], losses[-1])
""")
    assert "MESH-TRAIN-OK" in out


def test_train_entry_on_mesh_places_params_by_shard():
    """launch.train with a (1, 4) mesh: the state is initialised sharded,
    each device holds a share of the parameters, and steps run."""
    out = run_multidevice("""
import numpy as np
from repro.launch.mesh import make_test_mesh
from repro.launch.train import train
r = train("qwen3-235b-a22b", reduce=True, steps=2, batch=4, seq=32,
          mesh=make_test_mesh(data=1, model=4), log_every=1)
assert np.isfinite(r.losses).all() and r.restarts == 0, r
assert len(r.param_bytes) == 4, r.param_bytes
total = r.params * 4                      # float32
assert max(r.param_bytes) < total, (r.param_bytes, total)
print("TRAIN-MESH-OK", r.param_bytes)
""", n_devices=4)
    assert "TRAIN-MESH-OK" in out
