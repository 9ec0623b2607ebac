"""Replica weight streaming (`repro.moe.distribute`, DESIGN.md S2).

``materialize_replica_stack`` selects the slot table's rows from each
weight tensor first and packs only those for the wire.  The oracle below is
the earlier formulation, kept verbatim in spirit: encode every local
expert, pack all of them into one ``(E_local, 1, total)`` matrix, then
select and reduce-scatter.  Both must give the same bytes -- at EP=1, over
a flat 8-rank mesh and over a factored 2 x 4 (rack, lane) mesh, for every
wire codec and chunking, with empty slots -- and the same gradient onto the
mains (the training path's replica-gradient reduction).

The structural test keeps the full-width copies from coming back: at EP=1
no equation under the ``moe.distribute`` scope may produce more bytes than
the replica slots' own weights.
"""

import math

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import decode_wire, encode_wire
from repro.moe.distribute import materialize_replica_stack
from tests.helpers import run_multidevice

WIRES = ("none", "bf16", "int8")
CHUNKS = (1, 2)

# The pack-everything-then-select oracle.  Shared verbatim with the mesh
# snippets below, which run it in a subprocess with 8 virtual devices.
_ORACLE = '''
def oracle_select(w_local, flat, base):
    epr = w_local.shape[0]
    local_idx = flat - base
    in_range = (local_idx >= 0) & (local_idx < epr)
    rows = jnp.take(w_local, jnp.clip(local_idx, 0, epr - 1), axis=0)
    return jnp.where(in_range[:, None, None], rows,
                     jnp.zeros((), w_local.dtype))


def oracle_scatter(partial, axis_name, racks):
    R, n_slot, D, Fc = partial.shape
    if isinstance(axis_name, (tuple, list)):
        rack_axis, lane_axis = axis_name
        t = partial.reshape(racks, R // racks, n_slot, D, Fc)
        t = jax.lax.psum_scatter(t, lane_axis, scatter_dimension=1,
                                 tiled=False)
        return jax.lax.psum_scatter(t, rack_axis, scatter_dimension=0,
                                    tiled=False)
    return jax.lax.psum_scatter(partial, axis_name, scatter_dimension=0,
                                tiled=False)


def oracle_replicas(w_local, x_slots, my_rank, axis_name, n_chunks, racks):
    epr, D, F = w_local.shape
    R, n_slot = x_slots.shape
    flat = x_slots.reshape(-1)
    if axis_name is None:
        rep = oracle_select(w_local, flat, jnp.asarray(0, flat.dtype))
        return rep.reshape(R, n_slot, D, F)[0]
    base = (my_rank * epr).astype(flat.dtype)
    if n_chunks <= 1:
        partial = oracle_select(w_local, flat, base)
        return oracle_scatter(partial.reshape(R, n_slot, D, F), axis_name,
                              racks)
    chunk = -(-F // n_chunks)
    outs = []
    for c in range(n_chunks):
        lo = c * chunk
        w_c = jax.lax.dynamic_slice_in_dim(w_local, lo, min(chunk, F - lo), 2)
        partial = oracle_select(w_c, flat, base)
        outs.append(oracle_scatter(
            partial.reshape(R, n_slot, D, w_c.shape[-1]), axis_name, racks))
    return jnp.concatenate(outs, axis=-1)


def oracle_stack(ws, x_slots, my_rank, axis_name, n_chunks=1, racks=1,
                 wire_dtype="none"):
    epr = ws[0].shape[0]
    enc = [encode_wire(w, wire_dtype) for w in ws]
    sizes = [math.prod(w.shape[1:]) for w in enc]
    packed = jnp.concatenate([w.reshape(epr, 1, -1) for w in enc], axis=-1)
    rep = oracle_replicas(packed, x_slots, my_rank, axis_name, n_chunks,
                          racks)
    n_slot = rep.shape[0]
    out, off = [], 0
    for w, e, sz in zip(ws, enc, sizes):
        r = rep[:, 0, off:off + sz].reshape((n_slot,) + e.shape[1:])
        out.append(decode_wire(r, wire_dtype, w.dtype))
        off += sz
    return tuple(out)
'''
exec(_ORACLE)


def _weights(key, lead, D, F, dtype):
    k1, k3, k2 = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, lead + s).astype(dtype)
                 for k, s in ((k1, (D, F)), (k3, (D, F)), (k2, (F, D))))


# ------------------------------------------------------------- EP = 1 ----

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_chunks", CHUNKS)
@pytest.mark.parametrize("wire", WIRES)
def test_single_rank_matches_oracle(wire, n_chunks, dtype):
    """EP=1: replicas are local gathers, bit for bit the oracle's, with a
    zero replica for the empty slot."""
    ws = _weights(jax.random.PRNGKey(0), (4,), 8, 12, jnp.dtype(dtype))
    x_slots = jnp.asarray([[2, -1, 0]], jnp.int32)
    my = jnp.asarray(0, jnp.int32)
    got = materialize_replica_stack(ws, x_slots, my, None, n_chunks=n_chunks,
                                    wire_dtype=wire)
    want = oracle_stack(ws, x_slots, my, None, n_chunks, 1, wire)
    for g, w, main in zip(got, want, ws):
        assert g.shape == (3,) + main.shape[1:] and g.dtype == main.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w)), wire
        assert not np.asarray(g[1]).any(), "empty slot must stream zeros"
    if wire == "none":
        assert np.array_equal(np.asarray(got[0][0]), np.asarray(ws[0][2]))


def test_single_rank_gradient_matches_oracle():
    """EP=1: the gradient onto the mains is the oracle's segment-sum,
    with two slots bound to the same main."""
    ws = _weights(jax.random.PRNGKey(1), (4,), 8, 12, jnp.float32)
    x_slots = jnp.asarray([[2, -1, 2]], jnp.int32)
    my = jnp.asarray(0, jnp.int32)
    cots = _weights(jax.random.PRNGKey(2), (3,), 8, 12, jnp.float32)

    def loss(fn, ws):
        return sum((r * c).sum() for r, c in zip(fn(ws), cots))

    g_new = jax.grad(lambda ws: loss(lambda w: materialize_replica_stack(
        w, x_slots, my, None), ws))(ws)
    g_old = jax.grad(lambda ws: loss(lambda w: oracle_stack(
        w, x_slots, my, None), ws))(ws)
    for a, b in zip(g_new, g_old):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(g_new[0][2]),
                          np.asarray(cots[0][0] + cots[0][2]))


# ------------------------------------------------ real collectives ----

_MESH_SNIPPET = '''
import math
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.quantize import decode_wire, encode_wire
from repro.moe.distribute import materialize_replica_stack
''' + _ORACLE + '''
FACTORED = {factored}
R, epr, D, F, n_slot = 8, 2, 8, 12, 2
RACKS = 2 if FACTORED else 1
devs = np.array(jax.devices()[:R])
if FACTORED:
    mesh = Mesh(devs.reshape(RACKS, R // RACKS), ("rack", "lane"))
    axis = ("rack", "lane")
else:
    mesh = Mesh(devs.reshape(R), ("model",))
    axis = "model"
ep = axis if FACTORED else "model"
pk = jax.random.split(jax.random.PRNGKey(0), 6)
ws = (jax.random.normal(pk[0], (R, epr, D, F)),
      jax.random.normal(pk[1], (R, epr, D, F)),
      jax.random.normal(pk[2], (R, epr, F, D)))
cots = (jax.random.normal(pk[3], (R, n_slot, D, F)),
        jax.random.normal(pk[4], (R, n_slot, D, F)),
        jax.random.normal(pk[5], (R, n_slot, F, D)))
# Slot 0 pulls the next rank's first expert (cross-rack for some ranks on
# the factored mesh); slot 1 is empty on even ranks and, on odd ranks,
# binds expert 2 -- so expert 2's main feeds several replicas.
xs = np.full((R, n_slot), -1, np.int32)
xs[:, 0] = (np.arange(R) + 1) % R * epr
xs[1::2, 1] = 2
xs = jnp.asarray(xs)

def my_rank():
    if FACTORED:
        return (jax.lax.axis_index("rack") * (R // RACKS)
                + jax.lax.axis_index("lane"))
    return jax.lax.axis_index("model")

def sharded(fn, n_in, n_out):
    return jax.jit(jax.shard_map(fn, mesh=mesh, check_vma=False,
        in_specs=(P(ep),) * n_in + (P(None, None),),
        out_specs=(P(ep),) * n_out))

def stream(impl, wire, n_chunks):
    def body(w1, w3, w2, xs):
        out = impl((w1[0], w3[0], w2[0]), xs, my_rank(), axis,
                   n_chunks=n_chunks, racks=RACKS, wire_dtype=wire)
        return tuple(o[None] for o in out)
    return [np.array(o) for o in sharded(body, 3, 3)(*ws, xs)]

def new_impl(ws, xs, my, axis, n_chunks, racks, wire_dtype):
    return materialize_replica_stack(ws, xs, my, axis, n_chunks=n_chunks,
                                     racks=racks, wire_dtype=wire_dtype)

def old_impl(ws, xs, my, axis, n_chunks, racks, wire_dtype):
    return oracle_stack(ws, xs, my, axis, n_chunks, racks, wire_dtype)

for wire in ("none", "bf16", "int8"):
    for n_chunks in (1, 2):
        got = stream(new_impl, wire, n_chunks)
        want = stream(old_impl, wire, n_chunks)
        ok = all(np.array_equal(g, w) for g, w in zip(got, want))
        src = (np.arange(R) + 1) % R
        ok = ok and not got[0][0::2, 1].any()
        if wire == "none":
            ok = ok and np.array_equal(got[0][:, 0], np.array(ws[0])[src, 0])
        print(f"CASE {{wire}}-{{n_chunks}} {{'OK' if ok else 'FAIL'}}")

def grads(impl):
    def body(w1, w3, w2, c1, c3, c2, xs):
        def loss(w):
            out = impl(w, xs, my_rank(), axis, 2, RACKS, "none")
            return sum((o * c).sum() for o, c in zip(out, (c1[0], c3[0],
                                                           c2[0])))
        g = jax.grad(loss)((w1[0], w3[0], w2[0]))
        return tuple(x[None] for x in g)
    return [np.array(g) for g in sharded(body, 6, 3)(*ws, *cots, xs)]

g_new, g_old = grads(new_impl), grads(old_impl)
ok = all(np.array_equal(a, b) for a, b in zip(g_new, g_old))
# Expert 2 (rank 1's main 0) collects rank 0's slot 0 and the slot 1 of
# every odd rank.
want = np.array(cots[0])[0, 0] + np.array(cots[0])[1::2, 1].sum(axis=0)
ok = ok and np.allclose(g_new[0][1, 0], want, rtol=1e-6, atol=1e-6)
print(f"CASE grad {{'OK' if ok else 'FAIL'}}")
'''


def _mesh_results(factored: bool) -> dict[str, str]:
    out = run_multidevice(_MESH_SNIPPET.format(factored=factored))
    return dict(line.split()[1:] for line in out.splitlines()
                if line.startswith("CASE "))


@pytest.fixture(scope="module")
def flat_mesh():
    return _mesh_results(False)


@pytest.fixture(scope="module")
def rack_mesh():
    return _mesh_results(True)


MESH_CASES = [f"{w}-{c}" for w in WIRES for c in CHUNKS] + ["grad"]


@pytest.mark.parametrize("case", MESH_CASES)
def test_flat_mesh_matches_oracle(flat_mesh, case):
    """8-rank flat EP: one psum_scatter of the selected rows reproduces
    the oracle's replicas (and, for ``grad``, its gradient onto the
    mains) bit for bit."""
    assert flat_mesh.get(case) == "OK", flat_mesh


@pytest.mark.parametrize("case", MESH_CASES)
def test_rack_mesh_matches_oracle(rack_mesh, case):
    """2 racks x 4 lanes: the two-stage (lane, then rack) stream of the
    selected rows reproduces the oracle bit for bit."""
    assert rack_mesh.get(case) == "OK", rack_mesh


# ------------------------------------------------------- structure ----

def _leaf_eqns(jaxpr, scoped=False):
    """(eqn, under moe.distribute) for every first-order equation."""
    for eqn in jaxpr.eqns:
        inside = scoped or "moe.distribute" in str(eqn.source_info.name_stack)
        subs = [v for p in eqn.params.values()
                for v in (p if isinstance(p, (tuple, list)) else (p,))
                if isinstance(v, (jex_core.Jaxpr, jex_core.ClosedJaxpr))]
        if not subs:
            yield eqn, inside
        for sub in subs:
            yield from _leaf_eqns(getattr(sub, "jaxpr", sub), inside)


def test_distribute_moves_only_replica_rows():
    """At EP=1 the distribute scope writes the replica slots' weights and
    nothing of the size of the expert stack: no (E_local, ...) or
    (num_slots, ...) array, no equation producing more than N_slot experts'
    w1+w3+w2 bytes, and in all a few passes over those rows (slice,
    concatenate, mask), where one relayout of the mains would be 64 times
    the replicas' bytes."""
    from repro.core.balancer import BalancerConfig
    from repro.moe.gating import GatingConfig
    from repro.moe.layer import MoEConfig, init_moe_params
    from repro.moe.stages import run_staged_moe

    E, K, D, F, T, n_slot = 128, 2, 16, 32, 64, 2
    cfg = MoEConfig(gating=GatingConfig(num_experts=E, top_k=K),
                    balancer=BalancerConfig(mode="ultraep", n_slot=n_slot),
                    d_model=D, d_ff=F, ep_size=1, cap_pair=T * K,
                    cap_slot=T * K)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    jaxpr = jax.make_jaxpr(
        lambda x, p: run_staged_moe(x, p, cfg, axis_name=None))(x, params)

    weights = (params.w1, params.w3, params.w2)
    budget = n_slot * sum(w.nbytes for w in weights) // E
    scoped = [e for e, inside in _leaf_eqns(jaxpr.jaxpr) if inside]
    assert scoped, "no equation under moe.distribute"
    produced = 0
    for eqn in scoped:
        for v in eqn.outvars:
            aval = v.aval
            nbytes = math.prod(aval.shape) * aval.dtype.itemsize
            produced += nbytes
            assert not (aval.ndim >= 2 and aval.shape[0] in (E, E + n_slot)
                        ), (eqn.primitive, aval)
            assert nbytes <= budget, (eqn.primitive, aval)
    assert produced <= 5 * budget, (produced, budget)
