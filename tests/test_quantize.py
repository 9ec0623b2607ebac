"""Quantized wire + quantized expert compute (DESIGN.md S12).

Three independent directions of evidence:

* **codec**: the production wire codec (``repro.core.quantize``) against the
  dense numpy mirror in ``repro.moe.wire_oracle`` -- bitwise, both ways, so
  neither implementation vouches for itself.
* **transport**: the two-hop relabelling never looks inside a row, so the
  oracle's hop-by-hop permutation must equal the flat transpose bit for bit
  for raw fp32 payloads AND for encoded int8 rows with in-band scales.
* **engine**: the staged MoE layer on a real factored (2 racks x 4 lanes)
  virtual mesh -- routing counts and tier volumes bit-identical across wire
  dtypes (the codec touches payloads, never metadata), outputs within
  quantization tolerance of the fp32 path, and the reported ``tier_bytes``
  equal to ``tier_tokens`` times the wire payload width.

Plus the w8a8 grouped-SwiGLU kernel (TPU interpreter on CPU) against its q8
jnp reference (bitwise) and the fp32 reference (tolerance).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import (
    decode_int8,
    decode_wire,
    encode_int8,
    encode_wire,
    expert_wire_bytes,
    payload_bytes_per_item,
    quantize_rows,
    split_wire_int8,
    tensor_scale,
    wire_dtype_bytes,
)
from repro.moe import wire_oracle as wo
from tests.helpers import run_multidevice

# ------------------------------------------------------ codec primitives --


def test_rowwise_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.normal(size=(32, 64)) * 3.0, jnp.float32)
    q, scales = quantize_rows(x)
    assert q.dtype == jnp.int8 and scales.shape == (32,)
    y = decode_int8(q, scales[:, None])
    # Symmetric round-to-nearest: per-element error <= half a step.
    step = np.asarray(scales)[:, None]
    assert (np.abs(np.asarray(y - x)) <= 0.5 * step + 1e-7).all()


def test_zero_row_encodes_to_zero_scale(rng):
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32).at[2].set(0.0)
    q, scales = quantize_rows(x)
    # Exact 0 scale (no eps floor): zero rows ship zero bytes end to end,
    # which is what keeps the encoded replica reduce-scatter exact.
    assert float(scales[2]) == 0.0
    assert not np.asarray(q[2]).any()
    buf = encode_wire(x, "int8")
    assert not np.asarray(buf[2]).any()


def test_tensor_scale_keeps_eps_floor():
    # The grad-compression path divides by the scale unconditionally; the
    # all-zero tensor must still produce a positive scale there.
    assert float(tensor_scale(jnp.zeros((4, 4)))) > 0.0


def test_stochastic_rounding_is_unbiased():
    x = jnp.asarray([0.3, -1.7, 2.25, 0.01, -0.49] * 4, jnp.float32)
    scale = tensor_scale(x)
    keys = jax.random.split(jax.random.PRNGKey(0), 1024)
    qs = jax.vmap(lambda k: encode_int8(x, scale, key=k))(keys)
    mean = np.asarray(decode_int8(qs.astype(jnp.float32).mean(0), scale))
    # Deterministic rounding of 2.25/scale-style midpoints biases by up to a
    # half step; the stochastic mean must land within a few percent of one.
    assert np.abs(mean - np.asarray(x)).max() < 0.1 * float(scale)


def test_byte_helpers():
    assert wire_dtype_bytes("none") == 4
    assert wire_dtype_bytes("none", base_bytes=2) == 2
    assert wire_dtype_bytes("bf16") == 2
    assert wire_dtype_bytes("int8") == 1
    D, F = 64, 96
    assert payload_bytes_per_item(D, "none") == 4 * D
    assert payload_bytes_per_item(D, "bf16") == 2 * D
    assert payload_bytes_per_item(D, "int8") == D + 4
    assert expert_wire_bytes(D, F, "none") == 3 * D * F * 4
    # int8 expert stream: codes + one fp32 scale per encoded row
    # (w1/w3 are (D, F): D rows each; w2 is (F, D): F rows).
    assert expert_wire_bytes(D, F, "int8") == 3 * D * F + (2 * D + F) * 4
    with pytest.raises(ValueError):
        wire_dtype_bytes("fp4")


# ----------------------------------------- codec vs independent np mirror --


@pytest.mark.parametrize("wire", ["none", "bf16", "int8"])
def test_encode_wire_matches_np_mirror_bitwise(wire, rng):
    x = jnp.asarray(rng.normal(size=(8, 5, 32)) * 2.0, jnp.float32)
    x = x.at[1, 3].set(0.0)                      # a zero row in the mix
    prod = np.asarray(encode_wire(x, wire))
    mirror = wo.np_encode_wire(np.asarray(x), wire)
    assert prod.dtype == mirror.dtype
    assert np.array_equal(
        prod.view(np.uint8) if wire == "bf16" else prod,
        mirror.view(np.uint8) if wire == "bf16" else mirror)
    back = np.asarray(decode_wire(jnp.asarray(prod), wire, jnp.float32))
    assert np.array_equal(back, wo.np_decode_wire(mirror, wire))


def test_split_wire_int8_matches_decode(rng):
    x = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    buf = encode_wire(x, "int8")
    q, scales = split_wire_int8(buf)
    assert q.dtype == jnp.int8 and scales.shape == (6,)
    assert np.array_equal(np.asarray(decode_int8(q, scales[:, None])),
                          np.asarray(decode_wire(buf, "int8", jnp.float32)))


# ------------------------------------------------- oracle: two-hop wire ---


@pytest.mark.parametrize("racks", [2, 4])
def test_two_hop_oracle_equals_flat_bitwise(racks, rng):
    R, cap, D = 8, 6, 16
    send = rng.normal(size=(R, R, cap, D)).astype(np.float32)
    assert np.array_equal(wo.two_hop_wire(send, racks), wo.flat_wire(send))
    # The return wire runs the hops in the other order; same destination map.
    assert np.array_equal(wo.two_hop_wire(send, racks, reverse=True),
                          wo.flat_wire(send))


def test_two_hop_oracle_transports_encoded_rows_bitwise(rng):
    """Encoded int8 rows (codes + in-band scale lanes) ride the two-hop wire
    unchanged: transport never inspects the payload."""
    R, cap, D = 8, 4, 24
    send = rng.normal(size=(R, R, cap, D)).astype(np.float32) * 3.0
    enc = wo.np_encode_wire(send, "int8")
    assert enc.shape == (R, R, cap, D + 4) and enc.dtype == np.int8
    recv = wo.two_hop_wire(enc, racks=2)
    assert np.array_equal(recv, wo.flat_wire(enc))
    # Decode-after-transport == transport-of-decode, bit for bit.
    assert np.array_equal(wo.np_decode_wire(recv, "int8"),
                          wo.flat_wire(wo.np_decode_wire(enc, "int8")))


@pytest.mark.parametrize("wire", ["none", "bf16", "int8"])
def test_oracle_roundtrip_tolerance(wire, rng):
    R, cap, D = 8, 4, 16
    send = rng.normal(size=(R, R, cap, D)).astype(np.float32)
    dec, recv = wo.wire_roundtrip(send, wire, racks=2)
    want = wo.flat_wire(send)
    if wire == "none":
        assert np.array_equal(dec, want)
    else:
        np.testing.assert_allclose(dec, want, rtol=1e-2, atol=2e-2)
    # Production decode agrees bitwise with the mirror's receiver-side view.
    prod = np.asarray(decode_wire(jnp.asarray(recv), wire, jnp.float32))
    assert np.array_equal(prod, dec.astype(np.float32))


# -------------------------------------------- w8a8 grouped-SwiGLU kernel --


def _q8_operands(rng, G, M, K, N):
    x = jnp.asarray(rng.normal(size=(G, M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(G, K, N)) * K ** -0.5, jnp.float32)
    q, qs = quantize_rows(x)
    from repro.moe.expert import quantize_weight_cols

    wq, ws = quantize_weight_cols(w)
    return x, w, q, qs, wq, ws


def test_grouped_matmul_q8_kernel_matches_ref(rng, tpu_interpret):
    from repro.kernels.grouped_gemm import ops as gg
    from repro.kernels.grouped_gemm.ref import grouped_matmul_q8_ref

    G, M, K, N = 2, 128, 128, 128       # >= the tiny-fallback threshold
    x, w, q, qs, wq, ws = _q8_operands(rng, G, M, K, N)
    got = gg.grouped_matmul_q8(q, qs, wq, ws)
    ref = grouped_matmul_q8_ref(q, qs, wq, ws)
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    # And the q8 result tracks the fp32 product at quantization tolerance.
    full = jnp.einsum("gmk,gkn->gmn", x, w)
    err = np.abs(np.asarray(ref - full)).max() / np.abs(np.asarray(full)).max()
    assert err < 3e-2, err


def test_grouped_swiglu_q8_kernel_matches_ref(rng, tpu_interpret):
    from repro.kernels.grouped_gemm import ops as gg
    from repro.kernels.grouped_gemm.ref import grouped_swiglu_q8_ref

    G, M, K, N = 2, 128, 128, 128
    x, _, q, qs, _, _ = _q8_operands(rng, G, M, K, N)
    w1 = jnp.asarray(rng.normal(size=(G, K, N)) * K ** -0.5, jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(G, K, N)) * K ** -0.5, jnp.float32)
    from repro.moe.expert import quantize_weight_cols

    w1q, w1s = quantize_weight_cols(w1)
    w3q, w3s = quantize_weight_cols(w3)
    got = gg.grouped_swiglu_q8(q, qs, w1q, w1s, w3q, w3s)
    ref = grouped_swiglu_q8_ref(q, qs, w1q, w1s, w3q, w3s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    full = jax.nn.silu(jnp.einsum("gmk,gkn->gmn", x, w1)) \
        * jnp.einsum("gmk,gkn->gmn", x, w3)
    err = np.abs(np.asarray(ref - full)).max() / np.abs(np.asarray(full)).max()
    assert err < 5e-2, err


def test_grouped_ffn_int8_close_to_fp32(rng):
    from repro.moe.expert import grouped_ffn

    G, S, D, F = 4, 16, 32, 48
    xs = jnp.asarray(rng.normal(size=(G, S, D)), jnp.float32)
    valid = jnp.asarray(rng.random(size=(G, S)) < 0.8)
    xs = jnp.where(valid[..., None], xs, 0)   # dispatch zeroes the rest
    w1 = jnp.asarray(rng.normal(size=(G, D, F)) * D ** -0.5, jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(G, D, F)) * D ** -0.5, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(G, F, D)) * F ** -0.5, jnp.float32)
    base, _ = grouped_ffn(xs, valid, w1, w3, w2)
    q8, _ = grouped_ffn(xs, valid, w1, w3, w2, ffn_dtype="int8")
    # Invalid rows stay exactly zero either way.
    assert not np.asarray(q8)[~np.asarray(valid)].any()
    scale = np.abs(np.asarray(base)).max()
    assert np.abs(np.asarray(q8 - base)).max() / scale < 5e-2


# ------------------------------------------------ engine: single rank -----


def _layer_cfg(E, D, F, T, wire="none", ffn="none"):
    from repro.core.balancer import BalancerConfig
    from repro.moe.gating import GatingConfig
    from repro.moe.layer import MoEConfig

    return MoEConfig(
        gating=GatingConfig(num_experts=E, top_k=2),
        balancer=BalancerConfig(mode="ultraep", n_slot=2),
        d_model=D, d_ff=F, ep_size=1, cap_pair=T * 2, cap_slot=T * 2,
        wire_dtype=wire, ffn_dtype=ffn)


def test_layer_wire_dtypes_same_routing_close_output():
    from repro.moe.layer import init_moe_params, moe_layer_local

    E, D, F, T = 8, 16, 32, 64
    cfg0 = _layer_cfg(E, D, F, T)
    params = init_moe_params(jax.random.PRNGKey(0), cfg0)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    y0, _, s0 = moe_layer_local(x, params, cfg0, axis_name=None)
    for wire, ffn in (("bf16", "none"), ("int8", "none"), ("int8", "int8")):
        cfg = dataclasses.replace(cfg0, wire_dtype=wire, ffn_dtype=ffn)
        y, _, s = moe_layer_local(x, params, cfg, axis_name=None)
        assert np.array_equal(np.asarray(s.counts), np.asarray(s0.counts))
        assert int(s.drops_dispatch) == 0 and int(s.drops_slot) == 0
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y0), rtol=1e-2,
            atol=(1e-2 if ffn == "none" else 3e-2)
            * float(np.abs(np.asarray(y0)).max()),
            err_msg=f"wire={wire} ffn={ffn}")


def test_wire_and_ffn_dtype_validation():
    with pytest.raises(ValueError, match="wire_dtype"):
        _layer_cfg(8, 16, 32, 64, wire="fp8")
    with pytest.raises(ValueError, match="ffn_dtype"):
        _layer_cfg(8, 16, 32, 64, ffn="fp8")


# ------------------------------- engine: factored 2x4 mesh (subprocess) ---

_WIRE_MESH_SNIPPET = """
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
ep = ("rack", "model")

def run_case(wire, ffn):
    cfg = MoEConfig(gating=gcfg,
                    balancer=BalancerConfig(mode="ultraep", n_slot=2),
                    d_model=D, d_ff=F, ep_size=R, cap_pair=T*kk,
                    cap_slot=T*kk, distribute_chunks=2,
                    dispatch_mode="hier_a2a", racks=RACKS,
                    wire_dtype=wire, ffn_dtype=ffn)
    def run(x, router, w1, w3, w2):
        y, aux, stats = moe_layer_local(
            x, MoEParams(router, w1, w3, w2), cfg, axis_name=ep)
        drops = (stats.drops_dispatch + stats.drops_slot)[None]
        return (y, drops, stats.counts[None], stats.tier_tokens[None],
                stats.tier_bytes[None])
    f = jax.shard_map(run, mesh=mesh, check_vma=False,
        in_specs=(P(ep, None), P(None, None), P(ep, None, None),
                  P(ep, None, None), P(ep, None, None)),
        out_specs=(P(ep, None), P(ep), P(ep, None), P(ep, None),
                   P(ep, None)))
    y, drops, counts, tiers, tbytes = jax.jit(f)(x, router, w1, w3, w2)
    assert int(drops.sum()) == 0, (wire, ffn)
    return (np.array(y), np.array(counts), np.array(tiers[0]),
            np.array(tbytes[0]))

width = {"none": 4 * D, "bf16": 2 * D, "int8": D + 4}
y0, c0, t0, b0 = run_case("none", "none")
assert t0.sum() == T * kk, t0
assert np.array_equal(b0, t0 * width["none"]), (b0, t0)
scale = np.abs(y0).max()
for wire in ("bf16", "int8"):
    y, c, t, b = run_case(wire, "none")
    # Routing metadata rides the wire unencoded: bit-identical.
    assert np.array_equal(c, c0), wire
    assert np.array_equal(t, t0), wire
    assert np.array_equal(b, t0 * width[wire]), (wire, b)
    assert np.allclose(y, y0, rtol=1e-2, atol=1e-2 * scale), (
        wire, np.abs(y - y0).max() / scale)
y8, c8, t8, b8 = run_case("int8", "int8")
assert np.array_equal(c8, c0) and np.array_equal(t8, t0)
assert np.allclose(y8, y0, rtol=1e-2, atol=3e-2 * scale), (
    np.abs(y8 - y0).max() / scale)
print("WIRE-MESH-OK")
"""


def test_wire_dtypes_on_2x4_mesh():
    """Quantized wire over real collectives on the factored mesh: routing
    bit-identical across dtypes, outputs at tolerance, tier_bytes priced."""
    out = run_multidevice(_WIRE_MESH_SNIPPET)
    assert "WIRE-MESH-OK" in out


_REPLICA_WIRE_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.moe.distribute import materialize_replica_stack

R, epr, D, F = 8, 2, 8, 12
n_slot = 2
devs = np.array(jax.devices()[:R])
mesh = Mesh(devs.reshape(R), ("model",))
pk = jax.random.split(jax.random.PRNGKey(0), 3)
w1 = jax.random.normal(pk[0], (R, epr, D, F))
w3 = jax.random.normal(pk[1], (R, epr, D, F))
w2 = jax.random.normal(pk[2], (R, epr, F, D))
# Every rank pulls a replica of (rank+1)'s first local expert.
x_slots = np.full((R, n_slot), -1, np.int32)
x_slots[:, 0] = (np.arange(R) + 1) % R * epr
x_slots = jnp.asarray(x_slots)

def run(wire):
    def body(w1, w3, w2, xs):
        my = jax.lax.axis_index("model")
        out = materialize_replica_stack(
            [w1[0], w3[0], w2[0]], xs, my, "model", n_chunks=2,
            wire_dtype=wire)
        return tuple(o[None] for o in out)
    f = jax.shard_map(body, mesh=mesh, check_vma=False,
        in_specs=(P("model"), P("model"), P("model"), P(None, None)),
        out_specs=(P("model"), P("model"), P("model")))
    return [np.array(o) for o in jax.jit(f)(w1, w3, w2, x_slots)]

base = run("none")
for o, w in zip(base, [np.array(w1), np.array(w3), np.array(w2)]):
    src = (np.arange(R) + 1) % R
    assert np.array_equal(o[:, 0], w[src, 0]), "replica stream broken"
for o8, o0 in zip(run("int8"), base):
    # Per-row int8 with exact-zero scales: encode once at the home rank,
    # reduce-scatter the codes, decode at the receiver == decode at home.
    err = np.abs(o8 - o0).max() / np.abs(o0).max()
    assert err < 2e-2, err
for ob, o0 in zip(run("bf16"), base):
    assert np.allclose(ob, o0, rtol=8e-3, atol=8e-3)
print("REPLICA-WIRE-OK")
"""


def test_replica_stream_wire_on_mesh():
    """Tiered replica streaming with a quantized wire: the encoded
    reduce-scatter reproduces the home rank's encoding exactly, so the only
    error is the codec's."""
    out = run_multidevice(_REPLICA_WIRE_SNIPPET)
    assert "REPLICA-WIRE-OK" in out
