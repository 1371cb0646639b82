"""Jit'd multi-head attention wrapper over the flash kernel.

Handles batch folding, GQA head-group expansion and the decode path
(q_offset = KV-cache length).  On CPU the kernel runs in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import _flash_call
from repro.kernels.intersect.ops import default_interpret



@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "q_offset"))
def mha(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
        window: int = 0, softcap: float = 0.0, q_offset: int = 0
        ) -> jax.Array:
    """q [B, Sq, Hq, Dh]; k, v [B, Sk, Hkv, Dh] -> [B, Sq, Hq, Dh].

    GQA: Hq must be a multiple of Hkv; kv heads are repeated per group.
    """
    B, Sq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0
    rep = Hq // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / (Dh ** 0.5)
    qh = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, Dh)
    kh = k.transpose(0, 2, 1, 3).reshape(B * Hq, -1, Dh)
    vh = v.transpose(0, 2, 1, 3).reshape(B * Hq, -1, Dh)
    o = _flash_call(qh, kh, vh, causal=causal, window=window,
                    softcap=softcap, scale=scale, q_offset=q_offset,
                    interpret=default_interpret())
    return o.reshape(B, Hq, Sq, Dh).transpose(0, 2, 1, 3)
