"""Pallas TPU kernel: causal (optionally sliding-window) flash
attention forward, GQA-aware.

Canonical TPU formulation: grid (batch, q_head, q_blocks, kv_blocks)
with the kv dimension innermost; the online-softmax state (running max
m, normalizer l, f32 accumulator o) lives in VMEM scratch and is carried
across the kv grid steps.  Each program touches exactly one
(q_block x D) query tile and one (kv_block x D) kv tile — VMEM per
program is ~(q_block*D*4 + 2*kv_block*D*2 + q_block*D*4) bytes
(~0.4 MB at 128x128), leaving room for double buffering.

Causality/window: kv tiles that are fully masked for this q tile skip
their compute under ``pl.when`` (on TPU the grid still visits them, but
the MXU work is gated off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch_stats import LAUNCHES

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc, *,
            nk: int, q_block: int, kv_block: int, window: int, scale: float):
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q_start = qi * q_block
    k_start = j * kv_block
    # tile-level relevance: any (q, k) pair with k <= q and (window)
    relevant = k_start <= q_start + q_block - 1
    if window > 0:
        relevant &= (k_start + kv_block - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [qb, D]
        k = k_ref[0, 0].astype(jnp.float32)               # [kb, D]
        v = v_ref[0, 0].astype(jnp.float32)
        qpos = q_start + jax.lax.iota(jnp.int32, q_block)
        kpos = k_start + jax.lax.iota(jnp.int32, kv_block)
        s = q @ k.T
        mask = kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_acc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1)
        o_acc[...] = o_acc[...] * alpha[:, None] + p @ v
        m_acc[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o = o_acc[...] / jnp.maximum(l_acc[...], 1e-30)[:, None]
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, window: int = -1, q_block: int = 128,
                        kv_block: int = 128, interpret: bool = False):
    """q: [B, S, H, D]; k, v: [B, S, KV, D] -> [B, S, H, D]."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = min(q_block, S)
    kb = min(kv_block, S)
    pad_q = (-S) % qb
    pad_k = (-S) % kb
    Sq, Sk = S + pad_q, S + pad_k
    qt = jnp.moveaxis(q, 2, 1)                        # [B, H, S, D]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = Sq // qb, Sk // kb
    grid = (B, H, nq, nk)
    kern = functools.partial(
        _kernel, nk=nk, q_block=qb, kv_block=kb, window=window,
        scale=D ** -0.5,
    )
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kb, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, D), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_prefill",
    )(qt, kt, vt)
    out = out[:, :, :S]
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# single-token decode attention against a ring KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, scale: float):
    """One (batch, kv_head) program: the [G, C] score tile fits VMEM
    whole (C is the ring-cache length, bounded by max_len), so a plain
    masked softmax suffices — no online accumulation."""
    q = q_ref[0, 0].astype(jnp.float32) * scale      # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)              # [C, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, C]
    s = jnp.where(m_ref[...] > 0, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)              # [C, hd]
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_decode_fwd(q, k, v, valid, *, interpret: bool = False):
    """Single-token decode attention, GQA-aware.

    q: [B, 1, H, hd] (rope'd at the current position); k, v:
    [B, C, KV, hd] ring-cache contents; valid: [C] slot-validity mask
    (position occupied, causal, inside the window — computed by the
    caller with jnp, so traced windows/positions are fine).  Returns
    [B, 1, H, hd].  Masking by a precomputed slot mask keeps the kernel
    free of position arithmetic: ring order never matters to softmax.
    """
    B, _, H, hd = q.shape
    C, KV = k.shape[1], k.shape[2]
    G = H // KV
    LAUNCHES["flash_decode"] += 1
    q4 = q.reshape(B, 1, KV, G, hd)[:, 0]            # [B, KV, G, hd]
    kt = jnp.moveaxis(k, 2, 1)                       # [B, KV, C, hd]
    vt = jnp.moveaxis(v, 2, 1)
    mask = valid.astype(jnp.float32).reshape(1, C)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=hd ** -0.5),
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, C), lambda b, h: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(q4, kt, vt, mask)
    return out.reshape(B, 1, H, hd)
