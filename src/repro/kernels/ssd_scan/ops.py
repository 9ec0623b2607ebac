"""Public SSD chunk-scan op: Pallas intra-chunk + XLA cross-chunk scan."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_intra_chunk_pallas

__all__ = ["ssd_chunk_scan"]


def ssd_chunk_scan(xs, Bm, Cm, dt, da, *, initial_state=None):
    """Full SSD: kernelised intra-chunk + sequential inter-chunk recurrence.

    Same signature/semantics as models.ssm._ssd_chunk_scan_ref.
    """
    B, nc, Q, H, P = xs.shape
    N = Bm.shape[-1]
    y_intra, S_c, chunk_decay = ssd_intra_chunk_pallas(xs, Bm, Cm, dt, da)

    def scan_fn(s_prev, blk):
        s_new = s_prev * blk["decay"][:, :, None, None] + blk["S"]
        return s_new, s_prev

    init = (jnp.zeros((B, H, N, P), jnp.float32)
            if initial_state is None else initial_state.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        {"S": jnp.moveaxis(S_c, 1, 0), "decay": jnp.moveaxis(chunk_decay, 1, 0)},
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)
    cum = jnp.cumsum(da, axis=2)
    y_inter = jnp.einsum(
        "bcqhn,bchnp->bcqhp",
        Cm.astype(jnp.float32) * jnp.exp(cum)[..., None],
        prev_states)
    return y_intra + y_inter, final
