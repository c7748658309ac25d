"""Shared transformer building blocks.

Everything is a pure function over explicit parameter pytrees; stacks
are scanned (params stacked on a leading layer axis) where the layer
structure is uniform, unrolled otherwise (e.g. gemma3's mixed
local/global attention with per-kind cache shapes).

Attention is memory-efficient by construction: query-chunked online
softmax (flash-style) so an S x S score matrix is never materialized.
KV caches are ring buffers of length min(window, max_len) with an
explicit slot->position array, which makes full, sliding-window and
long-context decode masks uniform.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShardingPolicy

Array = jax.Array


def maybe_shard(x: Array, spec: Optional[P]) -> Array:
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def pvary(x, axes):
    """Mark ``x`` as varying over the manual axes ``axes`` (vma typing
    for scan carries created inside a shard_map region)."""
    if not axes:
        return x
    return jax.tree_util.tree_map(
        lambda t: jax.lax.pcast(t, tuple(axes), to="varying"), x)


# ---------------------------------------------------------------------------
# norms / embeddings / rope
# ---------------------------------------------------------------------------


def rmsnorm(x: Array, w: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: [..., S, H, hd]; positions: [..., S] (int)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32)[..., None, :] * 1.0
    # ang: [..., S, 1, 1] broadcasting against freqs [half]
    ang = positions.astype(jnp.float32)[..., None, None] * freqs  # [..., S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def embed(tokens: Array, table: Array) -> Array:
    if hasattr(table, "take_rows"):   # compressed serving table
        return table.take_rows(tokens)
    return jnp.take(table, tokens, axis=0)


def matmul(x: Array, w) -> Array:
    """``x @ w`` with a duck-typed hook for compressed serving weights
    (``serve.compressed.CompressedTensor``): anything exposing
    ``.matmul`` routes the contraction itself (sparse/quantized Pallas
    GEMMs), so models never import the serving layer."""
    if hasattr(w, "matmul"):
        return w.matmul(x)
    return x @ w


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def attn_params(key, cfg: ModelConfig, stacked: int | None):
    """Per-layer (or [L]-stacked) GQA projection weights."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pre = (stacked,) if stacked else ()
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], pre + (d, H * hd), cfg.pdtype),
        "wk": dense_init(ks[1], pre + (d, KV * hd), cfg.pdtype),
        "wv": dense_init(ks[2], pre + (d, KV * hd), cfg.pdtype),
        "wo": dense_init(ks[3], pre + (H * hd, d), cfg.pdtype),
    }


def mlp_params(key, cfg: ModelConfig, stacked: int | None, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    pre = (stacked,) if stacked else ()
    ks = jax.random.split(key, 3)
    return {
        "w1": dense_init(ks[0], pre + (d, ff), cfg.pdtype),
        "w3": dense_init(ks[1], pre + (d, ff), cfg.pdtype),
        "w2": dense_init(ks[2], pre + (ff, d), cfg.pdtype),
    }


def norm_params(cfg: ModelConfig, stacked: int | None):
    pre = (stacked,) if stacked else ()
    return jnp.zeros(pre + (cfg.d_model,), cfg.pdtype)


# ---------------------------------------------------------------------------
# attention core
# ---------------------------------------------------------------------------


NEG_INF = -1e30


def _gqa_scores(q: Array, k: Array) -> Array:
    """q: [B, Sq, KV, G, hd], k: [B, Sk, KV, hd] -> [B, KV, G, Sq, Sk]."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p: Array, v: Array) -> Array:
    """p: [B, KV, G, Sq, Sk], v: [B, Sk, KV, hd] -> [B, Sq, KV, G, hd]."""
    return jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(p.dtype))


def chunked_attention(
    q: Array,                # [B, S, H, hd] (already rope'd)
    k: Array,                # [B, S, KV, hd]
    v: Array,                # [B, S, KV, hd]
    *,
    window: int,             # -1 = full causal
    q_chunk: int,
    q_offset: Array | int = 0,  # global position of q[0] (prefill continuation)
) -> Array:
    """Causal (optionally sliding-window) attention, scanned over query
    chunks so peak score memory is O(q_chunk * S)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qc = min(q_chunk, S)
    pad = (-S) % qc
    nchunk = (S + pad) // qc

    qr = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qr = qr.reshape(B, nchunk, qc, KV, G, hd)
    kpos = jnp.arange(S)

    def one_chunk(ci, qchunk):
        # qchunk: [B, qc, KV, G, hd]; local (same-array) positions suffice
        # for causality since q and k index the same S tokens.
        qpos = ci * qc + jnp.arange(qc)
        s = _gqa_scores(qchunk.astype(jnp.float32) * scale, k.astype(jnp.float32))
        mask = kpos[None, :] <= qpos[:, None]
        # window may be a static int or a traced per-layer scalar (scan)
        if window is None:
            pass
        elif isinstance(window, (int, np.integer)):
            if window > 0:
                mask &= kpos[None, :] > qpos[:, None] - window
        else:
            w = jnp.asarray(window)
            mask &= jnp.where(w > 0, kpos[None, :] > qpos[:, None] - w, True)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_out(p, v)

    if nchunk == 1:
        out = one_chunk(0, qr[:, 0])[:, None]
    else:
        out = jax.lax.map(
            lambda args: one_chunk(args[0], args[1]),
            (jnp.arange(nchunk), jnp.moveaxis(qr, 1, 0)),
        )
        out = jnp.moveaxis(out, 0, 1)
    out = out.reshape(B, nchunk * qc, H, hd)[:, :S]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (ring buffer with slot->position map)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: Array      # [B, C, KV, hd]  (possibly [L, ...] stacked outside)
    v: Array      # [B, C, KV, hd]
    pos: Array    # [C] int32, -1 = empty; global position held by the slot


def init_kv_cache(cfg: ModelConfig, batch: int, window: int, max_len: int,
                  stacked: int | None = None, dtype=None) -> KVCache:
    C = max_len if window is None or window <= 0 else min(window, max_len)
    pre = (stacked,) if stacked else ()
    dt = dtype or cfg.adtype
    return KVCache(
        k=jnp.zeros(pre + (batch, C, cfg.n_kv_heads, cfg.hd), dt),
        v=jnp.zeros(pre + (batch, C, cfg.n_kv_heads, cfg.hd), dt),
        pos=jnp.full(pre + (C,), -1, jnp.int32),
    )


def cache_write(cache: KVCache, k_new: Array, v_new: Array, pos) -> KVCache:
    """Write one token (k_new/v_new: [B, 1, KV, hd]) at global ``pos``."""
    C = cache.k.shape[1]
    slot = jnp.mod(pos, C)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new.astype(cache.k.dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new.astype(cache.v.dtype), slot, axis=1)
    p = jax.lax.dynamic_update_slice_in_dim(
        cache.pos, jnp.asarray(pos, jnp.int32)[None], slot, axis=0
    )
    return KVCache(k, v, p)


def cache_prefill(cache: KVCache, k_all: Array, v_all: Array, S: int) -> KVCache:
    """Bulk-write positions [0, S) (S static).  For ring caches keep the
    last C positions."""
    C = cache.k.shape[1]
    if S <= C:
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_all.astype(cache.k.dtype), 0, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_all.astype(cache.v.dtype), 0, axis=1)
        p = jax.lax.dynamic_update_slice_in_dim(
            cache.pos, jnp.arange(S, dtype=jnp.int32), 0, axis=0
        )
        return KVCache(k, v, p)
    # keep last C tokens, ring-aligned so slot = pos % C stays true
    start = S - C
    kk = k_all[:, start:]
    vv = v_all[:, start:]
    pp = jnp.arange(start, S, dtype=jnp.int32)
    roll = jnp.mod(start, C)
    kk = jnp.roll(kk, roll, axis=1)
    vv = jnp.roll(vv, roll, axis=1)
    pp = jnp.roll(pp, roll, axis=0)
    return KVCache(kk.astype(cache.k.dtype), vv.astype(cache.v.dtype), pp)


def decode_attention(
    q: Array,                # [B, 1, H, hd] (rope'd at cur_pos)
    cache: KVCache,
    cur_pos,                 # scalar int (traced ok)
    window: int,
    use_pallas: bool = False,
) -> Array:
    B, _, H, hd = q.shape
    KV = cache.k.shape[2]
    G = H // KV
    valid = (cache.pos >= 0) & (cache.pos <= cur_pos)
    if window is None:
        pass
    elif isinstance(window, (int, np.integer)):
        if window > 0:
            valid &= cache.pos > cur_pos - window
    else:
        w = jnp.asarray(window)
        valid &= jnp.where(w > 0, cache.pos > cur_pos - w, True)
    if use_pallas:
        # slot validity is plain jnp, so unlike the prefill flash path
        # this works under scanned (traced) per-layer windows too
        from repro.kernels import ops as kops
        return kops.flash_decode(q, cache.k, cache.v, valid)
    scale = hd ** -0.5
    qr = q.reshape(B, 1, KV, G, hd).astype(jnp.float32) * scale
    s = _gqa_scores(qr, cache.k.astype(jnp.float32))  # [B, KV, G, 1, C]
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_out(p, cache.v)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged KV cache (shared page pool + per-request block tables)
# ---------------------------------------------------------------------------


class PagedKVCache(NamedTuple):
    """Shared KV page pool for one layer ([L, ...]-stacked outside).

    ``k``/``v``: [n_pages, page_size, KV, hd] arenas — the activation
    dtype, or int8 levels when the pool is quantized (inferred from the
    dtype; no flag field so the pytree structure is layout-independent).
    ``kscale``/``vscale``: [n_pages, page_size] f32 per-token-slot
    dequantization scales, zeros (and unread) for fp pools.

    Block tables and lengths live *outside* the pytree (one table per
    request, shared by every layer) — see ``serve/engine.py``.
    """

    k: Array
    v: Array
    kscale: Array
    vscale: Array


def init_paged_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                    stacked: int | None = None, quant: bool = False,
                    dtype=None) -> PagedKVCache:
    pre = (stacked,) if stacked else ()
    dt = jnp.int8 if quant else (dtype or cfg.adtype)
    shape = pre + (n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return PagedKVCache(
        k=jnp.zeros(shape, dt),
        v=jnp.zeros(shape, dt),
        kscale=jnp.zeros(pre + (n_pages, page_size), jnp.float32),
        vscale=jnp.zeros(pre + (n_pages, page_size), jnp.float32),
    )


def quantize_kv(x: Array) -> tuple[Array, Array]:
    """Deterministic symmetric int8 over the trailing [KV, hd] axes:
    scale = amax/127 per token slot (round-to-nearest, clip ±127), the
    same wire scheme as ``serve/compressed.py``'s QSGD levels — so a
    requantized identical token is bit-identical (admission re-feeds the
    last prompt token; idempotency keeps that step exact)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)[..., None, None]
    levels = jnp.clip(jnp.round(xf / safe), -127, 127).astype(jnp.int8)
    return levels, scale


def paged_write(pool: PagedKVCache, k_new: Array, v_new: Array,
                tables: Array, positions: Array,
                active: Array) -> PagedKVCache:
    """Write one token per slot (k_new/v_new: [B, 1, KV, hd]) into each
    slot's table-mapped page at ``positions`` ([B]).  Inactive slots
    (``active`` False — free engine slots still traced by the batched
    step) and unallocated (-1) table entries scatter to an out-of-pool
    sentinel and are dropped."""
    n_pages, ps = pool.k.shape[0], pool.k.shape[1]
    P = tables.shape[1]
    pidx = jnp.clip(positions // ps, 0, P - 1)
    pids = jnp.take_along_axis(tables, pidx[:, None], axis=1)[:, 0]
    pids = jnp.where(active & (pids >= 0), pids, n_pages)
    offs = jnp.mod(positions, ps)
    kv_k, kv_v = k_new[:, 0], v_new[:, 0]            # [B, KV, hd]
    if pool.k.dtype == jnp.int8:
        lk, sk = quantize_kv(kv_k)
        lv, sv = quantize_kv(kv_v)
        return pool._replace(
            k=pool.k.at[pids, offs].set(lk, mode="drop"),
            v=pool.v.at[pids, offs].set(lv, mode="drop"),
            kscale=pool.kscale.at[pids, offs].set(sk, mode="drop"),
            vscale=pool.vscale.at[pids, offs].set(sv, mode="drop"),
        )
    return pool._replace(
        k=pool.k.at[pids, offs].set(kv_k.astype(pool.k.dtype), mode="drop"),
        v=pool.v.at[pids, offs].set(kv_v.astype(pool.v.dtype), mode="drop"),
    )


def paged_prefill_insert(pool: PagedKVCache, k_all: Array, v_all: Array,
                         page_ids: Array) -> PagedKVCache:
    """Scatter one request's prefilled KV into the pool.

    k_all/v_all: [L, Cp, KV, hd] (Cp a page multiple); page_ids:
    [Cp/page_size] physical destinations in logical page order, with
    the ``n_pages`` sentinel marking unallocated tail pages (dropped).
    Pool is [L, ...]-stacked; quantization applied per token slot."""
    L, Cp, KV, hd = k_all.shape
    ps = pool.k.shape[2]
    n_adm = Cp // ps
    kp = k_all.reshape(L, n_adm, ps, KV, hd)
    vp = v_all.reshape(L, n_adm, ps, KV, hd)
    if pool.k.dtype == jnp.int8:
        lk, sk = quantize_kv(kp)
        lv, sv = quantize_kv(vp)
        return pool._replace(
            k=pool.k.at[:, page_ids].set(lk, mode="drop"),
            v=pool.v.at[:, page_ids].set(lv, mode="drop"),
            kscale=pool.kscale.at[:, page_ids].set(sk, mode="drop"),
            vscale=pool.vscale.at[:, page_ids].set(sv, mode="drop"),
        )
    return pool._replace(
        k=pool.k.at[:, page_ids].set(kp.astype(pool.k.dtype), mode="drop"),
        v=pool.v.at[:, page_ids].set(vp.astype(pool.v.dtype), mode="drop"),
    )


def paged_decode_attention(q: Array, pool: PagedKVCache, tables: Array,
                           lengths: Array, use_pallas: bool = False) -> Array:
    """Single-token attention against the page pool (full causal — the
    engine gates paged serving to uniform full-window configs).  Kernel
    or gather-oracle path by ``use_pallas``; a length-0 slot yields
    zeros either way."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.paged_decode(q, pool.k, pool.v, pool.kscale,
                                 pool.vscale, tables, lengths)
    from repro.kernels.ref import paged_decode_ref
    return paged_decode_ref(q, pool.k, pool.v, pool.kscale, pool.vscale,
                            tables, lengths)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def swiglu(x: Array, p) -> Array:
    h = jax.nn.silu(matmul(x, p["w1"])) * matmul(x, p["w3"])
    return matmul(h, p["w2"])


def gqa_project(x: Array, p, cfg: ModelConfig):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = matmul(x, p["wq"]).reshape(B, S, H, hd)
    k = matmul(x, p["wk"]).reshape(B, S, KV, hd)
    v = matmul(x, p["wv"]).reshape(B, S, KV, hd)
    return q, k, v


def attn_block_train(x, p, cfg: ModelConfig, window: int, positions,
                     policy: ShardingPolicy):
    """Full-sequence attention block (training / prefill). Returns
    (out, (k, v)) so prefill can populate the cache."""
    q, k, v = gqa_project(x, p, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # the Pallas kernel needs a static window (it shapes the kv loop);
    # traced per-layer windows (scanned mixed-pattern stacks) fall back
    # to the chunked-jnp path.
    if cfg.use_pallas and isinstance(window, (int, np.integer)):
        from repro.kernels import ops as kops
        o = kops.flash_attention(q, k, v, window=int(window))
    else:
        o = chunked_attention(q, k, v, window=window, q_chunk=cfg.q_chunk)
    B, S = x.shape[:2]
    out = matmul(o.reshape(B, S, cfg.n_heads * cfg.hd), p["wo"])
    return out, (k, v)


def attn_block_decode(x, p, cfg: ModelConfig, cache: KVCache, pos, window: int):
    q, k, v = gqa_project(x, p, cfg)
    posv = jnp.asarray(pos)[None]
    q = rope(q, jnp.broadcast_to(posv, (x.shape[0], 1)), cfg.rope_theta)
    k = rope(k, jnp.broadcast_to(posv, (x.shape[0], 1)), cfg.rope_theta)
    cache = cache_write(cache, k, v, pos)
    o = decode_attention(q, cache, pos, window, use_pallas=cfg.use_pallas)
    out = matmul(o.reshape(x.shape[0], 1, cfg.n_heads * cfg.hd), p["wo"])
    return out, cache


def attn_block_decode_paged(x, p, cfg: ModelConfig, pool: PagedKVCache,
                            tables: Array, positions: Array, active: Array):
    """Decode attention block over the shared page pool.  Unlike the
    contiguous block (one scalar ``pos``, vmapped per slot), this runs
    the whole slot batch at once: ``positions`` is [B] (per-slot rope
    phase) and ``active`` gates pool writes for free slots."""
    q, k, v = gqa_project(x, p, cfg)
    q = rope(q, positions[:, None], cfg.rope_theta)
    k = rope(k, positions[:, None], cfg.rope_theta)
    pool = paged_write(pool, k, v, tables, positions, active)
    lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
    o = paged_decode_attention(q, pool, tables, lengths,
                               use_pallas=cfg.use_pallas)
    out = matmul(o.reshape(x.shape[0], 1, cfg.n_heads * cfg.hd), p["wo"])
    return out, pool
