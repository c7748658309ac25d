"""Pallas TPU kernel: paged flash-decode attention over a shared KV
page pool (DESIGN.md §12).

The serving engine stores KV state in a fixed arena of
``[n_pages, page_size, KV, hd]`` blocks; each request owns a
*block table* — the ordered list of physical page ids holding its
tokens.  This kernel computes single-token GQA decode attention
directly against that layout: the block table rides in as a
scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the
``BlockSpec`` index maps gather each request's pages straight from the
pool — the contiguous per-request KV tensor never exists.

Grid: ``(batch, kv_head, page_blocks)`` with the page dimension
innermost.  One grid step fetches ``pages_per_block`` pages (the pool
operand is passed that many times, each copy with its own
table-indexed index map — the tunable geometry), and the
online-softmax state (running max m, normalizer l, f32 accumulator o)
is carried across page blocks in VMEM scratch, exactly like the
prefill flash kernel.

Quantized pages: when the pool dtype is int8 the per-page scale
vectors (``[n_pages, page_size]`` f32 — one scale per token slot, the
wire format mirroring ``qdq_gemm``'s per-row scale) ride along through
the same table-indexed gather and the dequantize multiply is fused
into the attention dot's VMEM residency.

TPU tiling: the kernel sees the pool as ``[n_pages, page_size, KV*hd]``
(a free reshape), so one block is a ``(page_size, hd)`` slab — hd lanes,
page_size sublanes.  Mosaic accepts it when hd is a multiple of 128 and
page_size a multiple of 8 (bf16: 16, int8: 32); smaller smoke
geometries run only in interpret mode.  Scales ride as ``[n_pages,
page_size, 1]`` columns, the lane-broadcast the dequantize needs.

Masking needs no position bookkeeping in the pool: pages are dense in
logical token order, so slot ``t`` of logical page ``j`` holds global
position ``j*page_size + t`` and validity is simply ``position <
length``.  Sentinel table entries (-1, unallocated) only ever cover
positions ≥ length, so clamping them to page 0 is safe.  A fully
masked request (length 0 — a free engine slot) returns exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch_stats import LAUNCHES

NEG_INF = -1e30

#: untuned fallback geometry (kernels/autotune.py tunes per shape)
DEFAULT_PAGES_PER_BLOCK = 4


def _paged_kernel(tbl_ref, len_ref, q_ref, *rest, nblk: int, pb: int,
                  ps: int, quant: bool, scale: float):
    if quant:
        k_refs, v_refs = rest[:pb], rest[pb:2 * pb]
        ks_refs, vs_refs = rest[2 * pb:3 * pb], rest[3 * pb:4 * pb]
        o_ref, o_acc, m_acc, l_acc = rest[4 * pb:]
    else:
        k_refs, v_refs = rest[:pb], rest[pb:2 * pb]
        ks_refs = vs_refs = ()
        o_ref, o_acc, m_acc, l_acc = rest[2 * pb:]
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [G, hd]
    length = len_ref[b]
    for i in range(pb):
        k = k_refs[i][0].astype(jnp.float32)             # [ps, hd]
        v = v_refs[i][0].astype(jnp.float32)
        if quant:
            k = k * ks_refs[i][0]                        # [ps, 1] scales
            v = v * vs_refs[i][0]
        kpos = (j * pb + i) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (1, ps), 1)
        live = kpos < length                             # [1, ps]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, ps]
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_acc[...]                              # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # explicit mask on p (not just on s): with every slot dead the
        # m subtraction would otherwise turn NEG_INF scores into
        # exp(0) = 1 and a free engine slot would emit garbage mass
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        o_acc[...] = o_acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_acc[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        o = o_acc[...] / jnp.maximum(l_acc[...], 1e-30)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def paged_decode_fwd(q, kp, vp, kscale, vscale, tables, lengths, *,
                     pages_per_block: int = DEFAULT_PAGES_PER_BLOCK,
                     interpret: bool = False):
    """Single-token decode attention against a KV page pool, GQA-aware.

    q: [B, 1, H, hd] (rope'd at each slot's position); kp/vp:
    [n_pages, page_size, KV, hd] pool arenas (f32/bf16, or int8 levels);
    kscale/vscale: [n_pages, page_size] f32 per-token-slot dequant
    scales (ignored for fp pools); tables: [B, max_pages] int32 block
    tables (-1 = unallocated); lengths: [B] int32 valid-token counts
    (0 = inactive slot → exact-zero output).  Returns [B, 1, H, hd].
    """
    B, _, H, hd = q.shape
    n_pages, ps, KV, _ = kp.shape
    G = H // KV
    P = tables.shape[1]
    quant = kp.dtype == jnp.int8
    pb = max(1, min(int(pages_per_block), P))
    pad = (-P) % pb
    # sentinel/-1 entries clamp to page 0: they only cover positions
    # beyond `lengths`, which the kernel masks by position anyway
    tbl = jnp.clip(tables, 0, n_pages - 1).astype(jnp.int32)
    if pad:
        tbl = jnp.pad(tbl, ((0, 0), (0, pad)))
    nblk = (P + pad) // pb
    LAUNCHES["paged_decode"] += 1
    q4 = q.reshape(B, 1, KV, G, hd)[:, 0]                # [B, KV, G, hd]
    # head h of a page is lanes [h*hd, (h+1)*hd) of the flattened view
    kp = kp.reshape(n_pages, ps, KV * hd)
    vp = vp.reshape(n_pages, ps, KV * hd)
    kscale = kscale.reshape(n_pages, ps, 1)
    vscale = vscale.reshape(n_pages, ps, 1)

    def page_spec(i):
        return pl.BlockSpec(
            (1, ps, hd),
            lambda b, h, j, tbl, lens, i=i: (tbl[b, j * pb + i], 0, h))

    def scale_spec(i):
        return pl.BlockSpec(
            (1, ps, 1),
            lambda b, h, j, tbl, lens, i=i: (tbl[b, j * pb + i], 0, 0))

    in_specs = [pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, j, tbl, lens: (b, h, 0, 0))]
    inputs = [q4]
    in_specs += [page_spec(i) for i in range(pb)]
    inputs += [kp] * pb
    in_specs += [page_spec(i) for i in range(pb)]
    inputs += [vp] * pb
    if quant:
        in_specs += [scale_spec(i) for i in range(pb)]
        inputs += [kscale] * pb
        in_specs += [scale_spec(i) for i in range(pb)]
        inputs += [vscale] * pb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nblk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, tbl, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, nblk=nblk, pb=pb, ps=ps,
                             quant=quant, scale=hd ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(tbl, jnp.asarray(lengths, jnp.int32), *inputs)
    return out.reshape(B, 1, H, hd)
