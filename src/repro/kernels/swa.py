"""Sliding-window attention as a 1-D stencil over the sequence (Casper on
gemma2's local layers).

A windowed-causal attention head attends to keys k in (q - W, q]: a fixed
offset neighborhood — precisely the access pattern Casper accelerates.  The
Casper recipe applies verbatim:

* query tiles of ``tq`` tokens are the "output stream";
* the KV window for tile i is the *element-offset* block
  [i*tq - (W-1), i*tq + tq) of the W-1 front-padded K/V — a tile+halo fetch
  (halo = W-1), i.e. the paper's unaligned load; one DMA per tile instead of
  one gather per (query, offset);
* the in-VMEM shifted products (scores) are the MACs.

GQA is handled by folding the q-heads-per-kv-head group into the query tile.
Softcapping (gemma2) optional.  Accumulation in f32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl



def element_blockspec(block_shape, index_map) -> pl.BlockSpec:
    """Element-offset BlockSpec: ``pl.Element`` per dim."""
    return pl.BlockSpec(tuple(pl.Element(b) for b in block_shape),
                        index_map)

NEG_INF = -1e30


def swa_ref(q: jax.Array, k: jax.Array, v: jax.Array, window: int,
            softcap: float | None = None) -> jax.Array:
    """Dense windowed-causal attention oracle (the kernel's ground
    truth).  q:(B,Hq,S,D), kv:(B,Hkv,S,D)."""
    b, hq, s, d = q.shape
    _, hkv, _, _ = k.shape
    g = hq // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(d)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv).astype(q.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, window, tq, softcap, scale):
    # q: (1, 1, G, tq, D); k/v: (1, 1, tq + window - 1, D)
    q = q_ref[0, 0].astype(jnp.float32)            # (G, tq, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (KW, D)
    v = v_ref[0, 0].astype(jnp.float32)
    g, _, d = q.shape
    kw = k.shape[0]

    i = pl.program_id(2)
    # Absolute positions. K/V were front-padded by window-1 zeros, so the
    # element at window index t corresponds to key position
    # i*tq - (window-1) + t.
    q_pos = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, kw), 0)
    k_pos = (i * tq - (window - 1)
             + jax.lax.broadcasted_iota(jnp.int32, (tq, kw), 1))
    valid = (k_pos >= 0) & (k_pos <= q_pos) & (k_pos > q_pos - window)

    s = jnp.einsum("gqd,kd->gqk", q, k) * scale    # (G, tq, KW)
    if softcap is not None:
        s = jnp.float32(softcap) * jnp.tanh(s / jnp.float32(softcap))
    s = jnp.where(valid[None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("gqk,kd->gqd", p, v) / denom
    o_ref[0, 0] = o.astype(o_ref.dtype)


def sliding_window_attention(
    q: jax.Array,       # (B, Hq, S, D)
    k: jax.Array,       # (B, Hkv, S, D)
    v: jax.Array,       # (B, Hkv, S, D)
    window: int,
    tq: int = 128,
    softcap: float | None = None,
    interpret: bool = True,
) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, _, _ = k.shape
    assert hq % hkv == 0
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)

    pad_s = -s % tq
    sp = s + pad_s
    qg = q.reshape(b, hkv, g, s, d)
    if pad_s:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad_s), (0, 0)))
    # Front-pad K/V by window-1 (zero keys are masked out by position).
    kp = jnp.pad(k, ((0, 0), (0, 0), (window - 1, pad_s), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (window - 1, pad_s), (0, 0)))
    kw = tq + window - 1

    kernel = functools.partial(_kernel, window=window, tq=tq,
                               softcap=softcap, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, sp // tq),
        in_specs=[
            pl.BlockSpec((1, 1, g, tq, d), lambda b_, h, i: (b_, h, 0, i, 0)),
            # element-offset windows (version-portable spelling; size-1
            # and full dims map identically under both conventions)
            element_blockspec((1, 1, kw, d),
                              lambda b_, h, i: (b_, h, i * tq, 0)),
            element_blockspec((1, 1, kw, d),
                              lambda b_, h, i: (b_, h, i * tq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, tq, d),
                               lambda b_, h, i: (b_, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, sp, d), q.dtype),
        interpret=interpret,
    )(qg, kp, vp)
    return out[:, :, :, :s].reshape(b, hq, s, d)
