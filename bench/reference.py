"""Plain float32 reference of a GQA / MoE language model, and its control.

It follows the published layer equations, as the program computes them
(each departure the configuration file lists under ``assumed``): RMS norm,
grouped-query attention with rotary positions over the whole head, an
optional bias on q, k and v and an optional norm on q and k, a SwiGLU
dense FFN, and a routed MoE (softmax or sigmoid scores, top-k, weights
renormalised and scaled, plus shared experts) with no capacity limit.
It imports nothing of the program and regenerates each weight from the
seed by its role (``bench.model.weight``).

The reference works layer by layer on one sequence.  Every layer but the
last runs at every position; the last runs only where logits are needed,
since nothing attends to its output.  Attention runs over blocks of
queries, and the routed experts over blocks of experts, so that the
float32 work fits beside the bfloat16 weights.

``precision="fp8"`` is the control: every matrix product takes its inputs
rounded to float8 (e4m3) with a scale per row of the activations and per
tensor of the weights, and accumulates in float32.
"""

from __future__ import annotations

from functools import partial

import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import weight

__all__ = ["Reference", "served_gaps"]

BLOCK_Q = 512
BLOCK_E = 16
BUCKET = 2048
EPS = 1e-6                     # the program's norm epsilon (see ``assumed``)


def _fp8(x, axis):
    """Round to float8 e4m3 with a max-abs scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, prec):
    """einsum of an activation ``a`` and a weight or activation ``b``."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if prec == "fp8":
        a = _fp8(a, -1)
        b = _fp8(b, None)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, w):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _swiglu(x, w1, w3, w2, prec):
    return _mm("...f,fd->...d",
               jax.nn.silu(_mm("...d,df->...f", x, w1, prec))
               * _mm("...d,df->...f", x, w3, prec), w2, prec)


class Reference:
    """The reference model of one configuration file and seed."""

    def __init__(self, c: dict, seed: int):
        self.c, self.seed = c, seed
        self.D, self.H = c["hidden_size"], c["num_attention_heads"]
        self.Hkv, self.hd = c["num_key_value_heads"], c["head_dim"]
        self.V, self.L = c["vocab_size"], c["num_hidden_layers"]
        self.E = c.get("n_routed_experts", c.get("num_experts", 0))
        self.first_dense = c.get("first_k_dense_replace", 0)
        self.dtype = jnp.dtype(c["program"]["dtype"])
        self.key = json.dumps(c, sort_keys=True)
        self.w = {}

    # The jitted layers take ``self`` as a static argument, so JAX's cache
    # of them keeps it: equal configurations share their programs, and
    # ``free`` lets the weights go while the cache holds the object.
    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, Reference) and other.key == self.key

    def free(self):
        self.w.clear()

    # ---- weights, bfloat16 as served, drawn once ----
    def _get(self, role, shape, dtype=None):
        if role not in self.w:
            self.w[role] = weight(self.seed, role, shape, dtype or self.dtype)
        return self.w[role]

    def layer_weights(self, l: int) -> dict:
        c, D, H, Hkv, hd = self.c, self.D, self.H, self.Hkv, self.hd
        p = f"layer{l}."
        w = {"norm1": self._get(p + "norm1", (D,)),
             "norm2": self._get(p + "norm2", (D,)),
             "wq": self._get(p + "attn.wq", (D, H * hd)),
             "wk": self._get(p + "attn.wk", (D, Hkv * hd)),
             "wv": self._get(p + "attn.wv", (D, Hkv * hd)),
             "wo": self._get(p + "attn.wo", (H * hd, D))}
        if c.get("attention_bias"):
            w["bq"] = self._get(p + "attn.bq", (H * hd,))
            w["bk"] = self._get(p + "attn.bk", (Hkv * hd,))
            w["bv"] = self._get(p + "attn.bv", (Hkv * hd,))
        if c["program"]["qk_norm"]:
            w["q_norm"] = self._get(p + "attn.q_norm", (hd,))
            w["k_norm"] = self._get(p + "attn.k_norm", (hd,))
        if self.E and l >= self.first_dense:
            F, E = c["moe_intermediate_size"], self.E
            w["router"] = self._get(p + "moe.router", (D, E), jnp.float32)
            w["w1"] = self._get(p + "moe.w1", (E, D, F))
            w["w3"] = self._get(p + "moe.w3", (E, D, F))
            w["w2"] = self._get(p + "moe.w2", (E, F, D))
            if c.get("n_shared_experts", 0):
                Fs = F * c["n_shared_experts"]
                w["sw1"] = self._get(p + "moe.shared_w1", (D, Fs))
                w["sw3"] = self._get(p + "moe.shared_w3", (D, Fs))
                w["sw2"] = self._get(p + "moe.shared_w2", (Fs, D))
        else:
            F = c["intermediate_size"]
            w["f1"] = self._get(p + "ffn.w1", (D, F))
            w["f3"] = self._get(p + "ffn.w3", (D, F))
            w["f2"] = self._get(p + "ffn.w2", (F, D))
        return w

    def top_weights(self) -> dict:
        D, V = self.D, self.V
        return {"embedding": self._get("embedding", (V, D)),
                "final_norm": self._get("final_norm", (D,)),
                "lm_head": self._get("lm_head", (V, D))}

    # ---- one layer ----
    def _qkv(self, w, h, pos, prec):
        S = h.shape[0]
        q = _mm("sd,dk->sk", h, w["wq"], prec)
        k = _mm("sd,dk->sk", h, w["wk"], prec)
        v = _mm("sd,dk->sk", h, w["wv"], prec)
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = q.reshape(S, self.H, self.hd)
        k = k.reshape(S, self.Hkv, self.hd)
        v = v.reshape(S, self.Hkv, self.hd)
        if "q_norm" in w:
            q, k = _norm(q, w["q_norm"]), _norm(k, w["k_norm"])
        theta = float(self.c["rope_theta"])
        return _rope(q, pos, theta), _rope(k, pos, theta), v

    def _attend(self, q, qpos, k, v, n_valid, prec):
        """Causal attention of queries at ``qpos`` over keys 0..n_valid-1."""
        rep = self.H // self.Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        s = _mm("qhd,khd->hqk", q, k, prec) * self.hd ** -0.5
        kpos = jnp.arange(k.shape[0])
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_valid)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, prec)

    def _ffn(self, w, h, prec):
        if "f1" in w:
            return _swiglu(h, w["f1"], w["f3"], w["f2"], prec)
        c = self.c
        logits = _mm("td,de->te", h, w["router"], prec)
        scores = (jax.nn.sigmoid(logits) if c["program"]["score_fn"]
                  == "sigmoid" else jax.nn.softmax(logits, axis=-1))
        k = c["num_experts_per_tok"]
        top, ids = jax.lax.top_k(scores, k)
        if c["norm_topk_prob"]:
            top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-20)
        top = top * c.get("routed_scaling_factor", 1.0)
        gate = jnp.zeros_like(scores).at[
            jnp.arange(h.shape[0])[:, None], ids].set(top)      # (T, E)

        def block(args):
            w1, w3, w2, g = args          # (Eb, D, F), ..., (T, Eb)
            a = (jax.nn.silu(_mm("td,edf->etf", h, w1, prec))
                 * _mm("td,edf->etf", h, w3, prec))
            y = _mm("etf,efd->etd", a, w2, prec)
            return jnp.einsum("te,etd->td", g, y,
                              precision=jax.lax.Precision.HIGHEST)

        E = self.E
        nb = E // BLOCK_E
        blocks = (w["w1"].reshape(nb, BLOCK_E, *w["w1"].shape[1:]),
                  w["w3"].reshape(nb, BLOCK_E, *w["w3"].shape[1:]),
                  w["w2"].reshape(nb, BLOCK_E, *w["w2"].shape[1:]),
                  jnp.moveaxis(gate.reshape(-1, nb, BLOCK_E), 1, 0))
        y = jax.lax.map(block, blocks).sum(0)
        if "sw1" in w:
            y = y + _swiglu(h, w["sw1"], w["sw3"], w["sw2"], prec)
        return y

    @partial(jax.jit, static_argnums=(0, 4))
    def _full_layer(self, w, x, n_valid, prec):
        """A layer at every position of a padded sequence (S, D)."""
        S = x.shape[0]
        pos = jnp.arange(S)
        q, k, v = self._qkv(w, _norm(x, w["norm1"]), pos, prec)
        nb = S // BLOCK_Q

        def blk(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * BLOCK_Q, BLOCK_Q)
            qp = jax.lax.dynamic_slice_in_dim(pos, i * BLOCK_Q, BLOCK_Q)
            return self._attend(qb, qp, k, v, n_valid, prec)

        att = jax.lax.map(blk, jnp.arange(nb)).reshape(S, -1)
        x = x + _mm("sk,kd->sd", att, w["wo"], prec)
        h = _norm(x, w["norm2"])
        if "f1" in w:
            return x + self._ffn(w, h, prec)

        def ffn_blk(i):
            hb = jax.lax.dynamic_slice_in_dim(h, i * BLOCK_Q, BLOCK_Q)
            return self._ffn(w, hb, prec)

        return x + jax.lax.map(ffn_blk, jnp.arange(nb)).reshape(S, -1)

    @partial(jax.jit, static_argnums=(0, 5))
    def _last_layer(self, w, top, x, rows, prec):
        """The last layer and the head at positions ``rows`` only."""
        S = x.shape[0]
        pos = jnp.arange(S)
        h = _norm(x, w["norm1"])
        _, k, v = self._qkv(w, h, pos, prec)
        hr = h[rows]
        q, _, _ = self._qkv(w, hr, rows, prec)
        att = self._attend(q, rows, k, v, S, prec).reshape(rows.shape[0], -1)
        xr = x[rows] + _mm("sk,kd->sd", att, w["wo"], prec)
        xr = xr + self._ffn(w, _norm(xr, w["norm2"]), prec)
        hf = _norm(xr, top["final_norm"])
        head = top["lm_head"]
        V = head.shape[0]
        return jax.lax.map(lambda hb: _mm("td,vd->tv", hf, hb, prec),
                           head.reshape(16, V // 16, -1)
                           ).transpose(1, 0, 2).reshape(hf.shape[0], V)

    def logits(self, tokens: np.ndarray, rows: np.ndarray,
               prec: str = "f32") -> np.ndarray:
        """Logits (len(rows), V) of ``tokens`` at positions ``rows``."""
        S = len(tokens)
        Sp = -(-S // BUCKET) * BUCKET
        top = self.top_weights()
        toks = jnp.asarray(np.pad(tokens, (0, Sp - S)), jnp.int32)
        x = jnp.take(top["embedding"], toks, axis=0).astype(jnp.float32)
        if prec == "fp8":
            x = _fp8(x, -1)
        for l in range(self.L - 1):
            x = self._full_layer(self.layer_weights(l), x, S, prec)
        rows_p = np.pad(rows, (0, -len(rows) % 16), mode="edge")
        out = self._last_layer(self.layer_weights(self.L - 1), top, x,
                               jnp.asarray(rows_p), prec)
        return np.asarray(out[:len(rows)])


def served_gaps(ref: Reference, prompt: np.ndarray, served: list[int],
                *, control: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """Per served token: how far below the reference's best logit the
    served token's logit lies, and the same for the token the control
    (the reference in fp8) puts first (None unless ``control``).  A gap
    of 0 is a token the reference puts first too."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    lg = ref.logits(seq, rows)
    best = lg.max(-1)
    gap = best - lg[np.arange(len(served)), served]
    cgap = None
    if control:
        cl = ref.logits(seq, rows, "fp8")
        cgap = best - lg[np.arange(len(served)), cl.argmax(-1)]
    return gap, cgap
