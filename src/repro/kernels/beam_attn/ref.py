"""Pure-jnp oracle for the beam shared-prefix attention kernel.

Layout matches the kernel's pre-arranged operands (see ops.py):

  q          : (R, kvH, M, hd)   with M = BW * G   (beams-major: row b*G+g)
  shared_k/v : (R, kvH, S, hd)
  shared_len : (R,) int32
  unshared_k/v : (R, kvH, ND, M, hd) — row m holds beam m // G's key
  step       : () int32 — unshared slots 0..step are valid
  returns    : (R, kvH, M, hd) float32
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def beam_attention_ref(q, shared_k, shared_v, shared_len,
                       unshared_k, unshared_v, step, scale: float):
    S = shared_k.shape[2]
    ND = unshared_k.shape[2]
    qf = q.astype(jnp.float32)

    # shared stage
    s1 = jnp.einsum("rhmd,rhsd->rhms", qf, shared_k.astype(jnp.float32)) * scale
    smask = (jnp.arange(S)[None, :] < shared_len[:, None])[:, None, None, :]
    s1 = jnp.where(smask, s1, NEG_INF)

    # unshared stage (per-beam keys)
    s2 = jnp.einsum("rhmd,rhnmd->rhmn", qf,
                    unshared_k.astype(jnp.float32)) * scale
    umask = (jnp.arange(ND) <= step)[None, None, None, :]
    s2 = jnp.where(umask, s2, NEG_INF)

    # joint softmax over S + ND columns
    m = jnp.maximum(jnp.max(s1, -1), jnp.max(s2, -1))
    p1 = jnp.exp(s1 - m[..., None])
    p2 = jnp.exp(s2 - m[..., None])
    l = jnp.sum(p1, -1) + jnp.sum(p2, -1)
    o1 = jnp.einsum("rhms,rhsd->rhmd", p1, shared_v.astype(jnp.float32))
    o2 = jnp.einsum("rhmn,rhnmd->rhmd", p2, unshared_v.astype(jnp.float32))
    return (o1 + o2) / jnp.maximum(l[..., None], 1e-30)
