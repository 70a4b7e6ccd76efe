"""Attention: GQA, rope, qk-norm, softcap, sliding window, KV cache.

Two implementations behind one interface:

* ``dense``     — full (Sq, Skv) score matrix; smoke tests & small shapes.
* ``blockwise`` — flash-style: static python loop over query tiles, lax.scan
                  over KV tiles with a running (m, l, acc).  Causal and
                  window masks restrict the *static* KV tile range per query
                  tile, so training/prefill work is triangular (or banded —
                  the banded case is exactly the Casper stencil tiling of
                  `kernels/swa.py`, in pure XLA for portability).

All score math in f32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.sharding import ShardCtx
from .common import PSpec, rms_norm, rope, softcap as _softcap

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    qk_norm: bool = False
    softcap: float | None = None
    window: int | None = None          # None = full causal
    causal: bool = True                # False for encoder self-attention
    rope_theta: float | None = 10000.0 # None = no rope (e.g. whisper)
    scale: float | None = None         # default 1/sqrt(d_head)
    block_q: int = 512
    block_k: int = 1024
    impl: str = "auto"                 # auto|dense|blockwise
    decode_seq_shard: bool = False     # flash-decode: KV seq over TP group
    fuse_qkv: bool = False             # one fused qkv einsum (1 read of x)

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv


def attn_param_specs(c: AttnCfg) -> dict[str, PSpec]:
    if c.fuse_qkv:
        p = {
            "wqkv": PSpec((c.d_model, c.n_heads + 2 * c.n_kv, c.d_head),
                          ("fsdp", "tp", None)),
            "wo": PSpec((c.n_heads, c.d_head, c.d_model),
                        ("tp", None, "fsdp")),
        }
    else:
        p = {
            "wq": PSpec((c.d_model, c.n_heads, c.d_head),
                        ("fsdp", "tp", None)),
            "wk": PSpec((c.d_model, c.n_kv, c.d_head), ("fsdp", "tp", None)),
            "wv": PSpec((c.d_model, c.n_kv, c.d_head), ("fsdp", "tp", None)),
            "wo": PSpec((c.n_heads, c.d_head, c.d_model),
                        ("tp", None, "fsdp")),
        }
    if c.qk_norm:
        p["q_norm"] = PSpec((c.d_head,), (None,), init="ones")
        p["k_norm"] = PSpec((c.d_head,), (None,), init="ones")
    return p


def _mask(q_pos, k_pos, c: AttnCfg, kv_len=None):
    """(Sq, Skv) boolean validity from absolute positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    valid = kp >= 0
    if c.causal:
        valid &= kp <= qp
    if c.window is not None:
        valid &= kp > qp - c.window
    if kv_len is not None:
        valid &= kp < kv_len
    return valid


def _sdpa_dense(q, k, v, q_pos, k_pos, c: AttnCfg, kv_len=None):
    # q: (B, Hkv, G, Sq, D); k/v: (B, Hkv, Skv, D)
    scale = c.scale or 1.0 / math.sqrt(c.d_head)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _softcap(s, c.softcap)
    valid = _mask(q_pos, k_pos, c, kv_len)        # (Sq, Skv)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bhgqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _sdpa_blockwise(q, k, v, q_pos0, c: AttnCfg, kv_len=None):
    """Flash-style attention.  q: (B,Hkv,G,Sq,D); k/v: (B,Hkv,Skv,D).

    ``q_pos0``: absolute position of q[...,0,:]; int (static) for
    training/prefill, traced scalar for decode.
    """
    b, h, g, sq, d = q.shape
    skv = k.shape[2]
    bq = min(c.block_q, sq)
    bk = min(c.block_k, skv)
    n_q = -(-sq // bq)
    n_k = -(-skv // bk)
    pad_q = n_q * bq - sq
    pad_k = n_k * bk - skv
    scale = c.scale or 1.0 / math.sqrt(c.d_head)
    static_pos = isinstance(q_pos0, int)

    if pad_q:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_len = skv if kv_len is None else kv_len
    kb = k.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)

    outs = []
    for qi in range(n_q):
        qblk = q[:, :, :, qi * bq:(qi + 1) * bq].astype(jnp.float32)
        # Static KV tile range for this query tile.
        if static_pos:
            q_lo = q_pos0 + qi * bq
            q_hi = q_lo + bq - 1
            hi = n_k if not c.causal else min(n_k, (q_hi // bk) + 1)
            lo = 0
            if c.window is not None:
                lo = max(0, (q_lo - c.window + 1) // bk)
        else:
            lo, hi = 0, n_k
        qpos = (q_pos0 + qi * bq + jnp.arange(bq))

        def body(carry, kv):
            m, l, acc = carry
            kblk, vblk, ki = kv
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qblk,
                           kblk.astype(jnp.float32)) * scale
            s = _softcap(s, c.softcap)
            kpos = ki * bk + jnp.arange(bk)
            valid = kpos[None, :] >= 0
            if c.causal:
                valid &= kpos[None, :] <= qpos[:, None]
            if c.window is not None:
                valid &= kpos[None, :] > qpos[:, None] - c.window
            if kv_len is not None:
                valid &= (kpos < kv_len)[None, :]
            s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                    vblk.astype(jnp.float32)))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, g, bq), jnp.float32)
        a0 = jnp.zeros((b, h, g, bq, d), jnp.float32)
        ks = jnp.arange(lo, hi)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (kb[lo:hi], vb[lo:hi], ks))
        outs.append(acc / jnp.maximum(l, 1e-30)[..., None])
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    return out[:, :, :, :sq].astype(q.dtype)


def _flash_decode_seqsharded(q, k, v, kv_len, c: AttnCfg, ctx):
    """Decode attention with the KV cache sequence-sharded over the TP
    ('model') axis — flash-decoding: each shard reduces its KV slice with a
    local running softmax; partials combine with pmax/psum (tiny payloads:
    (B, H, D) per device vs. reading a replicated multi-GB cache).

    q: (B, Hkv, G, 1, D) replicated over model; k/v: (B, Hkv, S, D) with S
    sharded over 'model'.  Returns (B, Hkv, G, 1, D).
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding import _mesh_axes

    mesh = ctx.mesh
    dp = _mesh_axes(mesh, "dp")
    scale = c.scale or 1.0 / math.sqrt(c.d_head)
    b = q.shape[0]
    dp_ok = (b % _axis_size(mesh, dp) == 0)
    bax = dp if dp_ok else None

    def body(q_, k_, v_, kv_len_):
        i = jax.lax.axis_index("model")
        s_loc = k_.shape[2]
        kpos = i * s_loc + jnp.arange(s_loc)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", q_.astype(jnp.float32),
                       k_.astype(jnp.float32)) * scale
        s = _softcap(s, c.softcap)
        valid = kpos < kv_len_
        if c.window is not None:
            valid &= kpos > (kv_len_ - 1) - c.window
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        m_g = jax.lax.pmax(m, "model")
        p = jnp.exp(s - m_g[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), "model")
        acc = jax.lax.psum(
            jnp.einsum("bhgqk,bhkd->bhgqd", p, v_.astype(jnp.float32)),
            "model")
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bax, None, None, None, None),
                  P(bax, None, "model", None),
                  P(bax, None, "model", None), P()),
        out_specs=P(bax, None, None, None, None),
        check_vma=False)
    return fn(q, k, v, jnp.int32(kv_len))


def _axis_size(mesh, axes) -> int:
    n = 1
    for a in ((axes,) if isinstance(axes, str) else axes):
        n *= mesh.shape[a]
    return n


def make_cache(c: AttnCfg, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    shape = (batch, c.n_kv, max_len, c.d_head)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def attention(
    p: dict,
    x: jax.Array,                 # (B, S, D)
    c: AttnCfg,
    ctx: ShardCtx,
    pos0: int | jax.Array = 0,    # absolute position of x[:, 0]
    cache: dict | None = None,    # mutated-by-copy KV cache (decode)
    cache_len: jax.Array | None = None,   # filled length of cache
    kv_x: jax.Array | None = None,        # cross-attention source
) -> tuple[jax.Array, dict | None]:
    b, s, _ = x.shape
    if c.fuse_qkv and kv_x is None:
        qkv = jnp.einsum("bsd,dhk->bhsk", x, p["wqkv"])
        q = qkv[:, :c.n_heads]
        k = qkv[:, c.n_heads:c.n_heads + c.n_kv]
        v = qkv[:, c.n_heads + c.n_kv:]
    else:
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
        src = x if kv_x is None else kv_x
        k = jnp.einsum("bsd,dhk->bhsk", src, p["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", src, p["wv"])

    if c.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    if c.rope_theta is not None:
        qpos = pos0 + jnp.arange(s)
        q = rope(q, qpos[None, None, :], c.rope_theta)
        if kv_x is None:
            k = rope(k, qpos[None, None, :], c.rope_theta)

    q = ctx.constrain(q, "dp", "tp", None, None)
    k = ctx.constrain(k, "dp", "tp", None, None)
    v = ctx.constrain(v, "dp", "tp", None, None)

    new_cache = None
    kv_len = None
    if cache is not None:
        if kv_x is None:
            idx = cache_len if cache_len is not None else 0
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                     k.astype(cache["k"].dtype),
                                                     idx, axis=2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                     v.astype(cache["v"].dtype),
                                                     idx, axis=2)
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv
            kv_len = (cache_len + s) if cache_len is not None else s
        else:
            # cross-attention: cache holds the precomputed encoder K/V
            k, v = cache["k"], cache["v"]
            new_cache = cache

    g = c.group
    qg = q.reshape(b, c.n_kv, g, s, c.d_head)

    skv = k.shape[2]
    if (c.decode_seq_shard and cache is not None and s == 1 and kv_x is None
            and ctx.mesh is not None and "model" in ctx.mesh.axis_names):
        o = _flash_decode_seqsharded(qg, k, v, kv_len, c, ctx)
        o = o.reshape(b, c.n_heads, s, c.d_head)
        y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
        return ctx.constrain(y, "dp", None, None), new_cache

    impl = c.impl
    if impl == "auto":
        impl = "dense" if (s * skv <= 512 * 512) else "blockwise"
    if impl == "dense":
        qpos_arr = pos0 + jnp.arange(s)
        kpos_arr = jnp.arange(skv) if (cache is not None or kv_x is not None) \
            else pos0 + jnp.arange(skv)
        o = _sdpa_dense(qg, k, v, qpos_arr, kpos_arr, c, kv_len)
    else:
        o = _sdpa_blockwise(qg, k, v, pos0, c, kv_len)

    o = o.reshape(b, c.n_heads, s, c.d_head)
    y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    y = ctx.constrain(y, "dp", None, None)
    return y, new_cache
