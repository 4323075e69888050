"""jnp reference of the held experts' part of an expert layer: each held
expert applied densely to every token in float32 at 'highest', masked by
the assignments that pick it."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn_ref(x, wg, wi, wo, local, weight, base):
    """Same contract as :func:`repro.kernels.expert_ffn.expert_ffn`;
    returns float32 ``(T, d)`` and the held assignments' count."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    x = x.astype(f32)
    out = base.astype(f32)
    held = 0
    for e in range(wg.shape[0]):
        pick = jnp.sum(jnp.where(local == e, weight.astype(f32), 0.0), -1)
        g = jnp.dot(x, wg[e].astype(f32), precision=hi)
        u = jnp.dot(x, wi[e].astype(f32), precision=hi)
        y = jnp.dot(jax.nn.silu(g) * u, wo[e].astype(f32), precision=hi)
        out = out + pick[:, None] * y
        held = held + jnp.sum(local == e)
    return out, held
