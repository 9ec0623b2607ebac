"""A configuration file as the program runs it, and its seeded weights.

:func:`model_config` maps a configuration's published keys onto the
program's ``ModelConfig``.  :func:`make_params` draws every leaf of the
program's parameter tree on the device, in one jitted call, from the seed
and the leaf's role; :func:`weight` draws the same leaf again for the
reference by its role alone, so the reference takes nothing the program
made.  Shapes and dtypes come from ``jax.eval_shape`` of the program's
``init_lm``; the scales are the benchmark's, chosen so that the residual
stream is of unit scale and routing follows the tokens.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

__all__ = ["model_config", "runtime_config", "base_key", "leaf_roles",
           "make_params", "weight", "scale"]


def model_config(c: dict):
    from repro.configs.base import ModelConfig, MoEArch

    p = c["program"]
    experts = c.get("n_routed_experts", c.get("num_experts", 0))
    moe = None
    if experts:
        moe = MoEArch(
            num_experts=experts, top_k=c["num_experts_per_tok"],
            d_ff=c["moe_intermediate_size"], score_fn=p["score_fn"],
            norm_topk_prob=c["norm_topk_prob"],
            aux_loss_weight=p["aux_loss_weight"], use_bias=p["use_bias"],
            routed_scaling=c.get("routed_scaling_factor", 1.0),
            n_shared_experts=c.get("n_shared_experts", 0),
            shared_d_ff=(c["moe_intermediate_size"]
                         if c.get("n_shared_experts", 0) else 0),
            first_dense_layers=c.get("first_k_dense_replace", 0),
            n_slot=p["n_slot"])
    return ModelConfig(
        name=c["name"], family="moe" if moe else "dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        vocab_size=c["vocab_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        qkv_bias=c.get("attention_bias", False), qk_norm=p["qk_norm"],
        rope_theta=float(c["rope_theta"]), d_ff=c["intermediate_size"],
        moe=moe, tie_embeddings=c.get("tie_word_embeddings", False),
        source=c["source"])


def runtime_config(c: dict, *, balancer: str, cf_pair: float,
                   cf_slot: float, remat: bool = False):
    from repro.core.balancer import BalancerConfig
    from repro.models.transformer import RuntimeConfig

    return RuntimeConfig(
        balancer=BalancerConfig(mode=balancer, n_slot=c["program"]["n_slot"]),
        cf_pair=cf_pair, cf_slot=cf_slot, scan_layers=True, remat=remat,
        dtype=jnp.dtype(c["program"]["dtype"]))


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              seed // 2**31)


# Standard deviation of each role, by its fan-in; norms are 1 + noise.
def scale(role: str, shape: tuple) -> tuple[float, float]:
    """(mean, standard deviation) of a leaf's entries."""
    kind = role.rsplit(".", 1)[-1]
    if kind.endswith("norm") or kind in ("norm1", "norm2", "q_norm",
                                         "k_norm"):
        return 1.0, 0.05
    if kind == "embedding":
        return 0.0, 1.0
    if kind in ("bq", "bk", "bv"):
        return 0.0, 0.1
    fan_in = shape[-1] if kind == "lm_head" else shape[-2]
    return 0.0, fan_in ** -0.5


def _role(path) -> list:
    """The names and indices along a leaf's path in the parameter tree."""
    names = []
    for k in path:
        if hasattr(k, "name"):
            names.append(k.name)
        elif hasattr(k, "idx"):
            names.append(k.idx)
    return names


def leaf_roles(cfg, rcfg, shapes) -> list:
    """(role, shape, dtype, stacked) per leaf of the program's tree, where a
    stacked leaf carries one slice per layer on its leading axis."""
    from repro.models.transformer import segments_for

    segs = segments_for(cfg, rcfg)
    ffn_names = ("w1", "w3", "w2")
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = _role(path)
        if n[0] != "segments":
            out.append((n[0], None, leaf))
            continue
        seg = segs[n[1]]
        rest = n[2:]
        if isinstance(rest[0], int):           # tuple of unstacked blocks
            layers = [seg.layer_ids[rest[0]]]
            rest = rest[1:]
        else:
            layers = list(seg.layer_ids)
        if rest[0] == "ffn":
            rest = ["ffn", ffn_names[rest[1]]]
        out.append((".".join(map(str, rest)), layers, leaf))
    return out


def _draw(key: jax.Array, role: str, shape, dtype) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(role.encode()))
    mean, std = scale(role, shape)
    x = jax.random.normal(key, shape, jnp.float32) * std + mean
    return x.astype(dtype)


def weight(seed: int, role: str, shape, dtype) -> jax.Array:
    """One weight by its role, e.g. ``layer1.moe.w1`` or ``lm_head``."""
    return _draw_jit(base_key(seed), role, tuple(shape), jnp.dtype(dtype))


_draw_jit = jax.jit(_draw, static_argnums=(1, 2, 3))


def make_params(seed: int, cfg, rcfg, pctx, *, out_shardings=None):
    """The program's parameter tree, drawn on the device in one call."""
    from repro.models.model import init_lm

    shapes = jax.eval_shape(
        lambda k: init_lm(k, cfg, rcfg, pctx), jax.random.PRNGKey(0))
    roles = leaf_roles(cfg, rcfg, shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def build(key):
        leaves = []
        for role, layers, leaf in roles:
            if layers is None:
                leaves.append(_draw(key, role, leaf.shape, leaf.dtype))
            elif leaf.shape and len(layers) == leaf.shape[0] and len(
                    layers) > 1:
                leaves.append(jnp.stack([
                    _draw(key, f"layer{i}.{role}", leaf.shape[1:],
                          leaf.dtype) for i in layers]))
            else:
                leaves.append(_draw(key, f"layer{layers[0]}.{role}",
                                    leaf.shape, leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))
