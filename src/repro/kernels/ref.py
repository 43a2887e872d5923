"""Pure-jnp oracles for every Pallas kernel — the correctness references
the per-kernel tests sweep shapes/dtypes against."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def fingerprint_rows(cols: tuple, salt: int = 0):
    """Must stay bit-identical to relational.fingerprint_rows."""
    from repro.core.relational import fingerprint_rows as _fp

    return _fp(cols, salt)


def segment_softmax(scores, segment_ids, num_segments, eps: float = 1e-9):
    seg = segment_ids.astype(jnp.int32)
    mx = jax.ops.segment_max(scores, seg, num_segments)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    ex = jnp.exp(scores - mx[jnp.clip(seg, 0, num_segments - 1)])
    den = jax.ops.segment_sum(ex, seg, num_segments)
    return ex / (den[jnp.clip(seg, 0, num_segments - 1)] + eps)
