"""Pure-jnp oracles for every Pallas kernel.

These define the exact semantics the kernels must reproduce; the tests
sweep shapes/dtypes and assert allclose between kernel (interpret=True
on CPU) and these references.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _vary_like(x: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """``x`` typed as varying over the manual axes ``like`` varies over:
    inside a ``shard_map`` a loop carry built from a constant must match
    the type the loop body gives it."""
    axes = tuple(jax.typeof(like).vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, axes, to="varying") if axes else x


# ---------------------------------------------------------------------------
# topk_compress: bisection-threshold top-k select + fused error update
# ---------------------------------------------------------------------------


def topk_compress_ref(acc: jnp.ndarray, k: int, *, iters: int = 24,
                      sign: bool = False):
    """acc: [rows, n] error-compensated accumulator (m + x - x̂).

    Per row: bisect (``iters`` rounds) for the magnitude threshold of the
    k-th largest entry of |acc|; select the survivors (full precision, or
    sign * ||sel||_2/count when ``sign``); the fused error update is
    m' = acc - selected.

    Selection is *exactly* k generically: the bisection invariant is
    cnt(a >= lo) > k >= cnt(a >= hi), so the hi threshold keeps exactly
    k entries once the interval is narrower than the k-th/(k+1)-th
    magnitude gap.  Under ties or an exhausted iteration budget it falls
    back to the lo threshold (>= k survivors, a strictly better
    sparsifier; the error memory absorbs the difference either way).

    Returns (selected, new_memory, count_per_row).
    """
    a = jnp.abs(acc.astype(jnp.float32))
    hi = jnp.max(a, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(a >= mid, axis=1, keepdims=True)
        # too many kept -> raise threshold; too few -> lower it
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    c_hi = jnp.sum(a >= hi, axis=1, keepdims=True)
    thr = jnp.where(c_hi >= k, hi, lo)
    # exact zeros are never survivors: an all-zero (or zero-padded) row
    # must not count toward the wire-bits ledger
    mask = (a >= thr) & (a > 0.0)
    cnt = jnp.sum(mask, axis=1)
    sel = jnp.where(mask, acc.astype(jnp.float32), 0.0)
    if sign:
        norm = jnp.sqrt(jnp.sum(jnp.square(sel), axis=1, keepdims=True))
        denom = jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
        sel = jnp.where(mask, jnp.sign(acc) * norm / denom, 0.0)
    new_mem = acc.astype(jnp.float32) - sel
    return sel, new_mem, cnt


def topk_compact_ref(acc: jnp.ndarray, k: int, kcap: int, *,
                     iters: int = 24, sign: bool = False, chunk: int = 256):
    """Oracle for the compact-emitting kernel (``topk_compact``).

    Same threshold selection as :func:`topk_compress_ref`; survivors are
    then compacted into ``(idx, val)`` buffers of capacity ``kcap`` per
    row, slots filled in ascending index order, empty slots carrying the
    out-of-row sentinel ``(idx=n, val=0)`` that a scatter-add decoder
    drops.  Survivors past ``kcap`` (heavy ties only) stay in the error
    memory instead of crossing the wire.

    Deliberately sort- and scatter-free (prefix-sum slots + chunked
    one-hot contraction), like the kernel: this is also the compact
    path for rows the kernel does not take.

    Returns (idx [rows, kcap] int32, val [rows, kcap] f32,
    new_mem [rows, n] f32, cnt [rows] int32).
    """
    acc = acc.astype(jnp.float32)
    rows, n = acc.shape
    a = jnp.abs(acc)
    hi = jnp.max(a, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum(a >= mid, axis=1, keepdims=True)
        lo = jnp.where(cnt > k, mid, lo)
        hi = jnp.where(cnt > k, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    c_hi = jnp.sum(a >= hi, axis=1, keepdims=True)
    thr = jnp.where(c_hi >= k, hi, lo)
    mask = (a >= thr) & (a > 0.0)
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1
    emit = mask & (pos < kcap)
    cnt = jnp.sum(emit, axis=1).astype(jnp.int32)
    sel = jnp.where(emit, acc, 0.0)
    if sign:
        norm = jnp.sqrt(jnp.sum(jnp.square(sel), axis=1, keepdims=True))
        denom = jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
        sel = jnp.where(emit, jnp.sign(acc) * norm / denom, 0.0)
    new_mem = acc - sel
    # chunked one-hot contraction bounds the [rows, chunk, kcap]
    # intermediate; rows are zero-padded to a chunk multiple (padding
    # never emits).
    pad = (-n) % chunk
    if pad:
        pos = jnp.pad(pos, ((0, 0), (0, pad)))
        emit = jnp.pad(emit, ((0, 0), (0, pad)))
        sel_p = jnp.pad(sel, ((0, 0), (0, pad)))
    else:
        sel_p = sel
    cols = jnp.arange(kcap)[None, None, :]
    lane = jnp.arange(chunk)[None, :]

    def cbody(g, carry):
        idx_acc, val_acc = carry
        p = jax.lax.dynamic_slice(pos, (0, g * chunk), (rows, chunk))
        e = jax.lax.dynamic_slice(emit, (0, g * chunk), (rows, chunk))
        v = jax.lax.dynamic_slice(sel_p, (0, g * chunk), (rows, chunk))
        oh = ((p[:, :, None] == cols) & e[:, :, None]).astype(jnp.float32)
        gidx = jnp.broadcast_to((g * chunk + lane).astype(jnp.float32),
                                (rows, chunk))
        # exact f32 products: TPU's default matmul precision is bf16
        val_acc = val_acc + jnp.einsum("rc,rcj->rj", v, oh,
                                       precision=jax.lax.Precision.HIGHEST)
        idx_acc = idx_acc + jnp.einsum("rc,rcj->rj", gidx, oh,
                                       precision=jax.lax.Precision.HIGHEST)
        return idx_acc, val_acc

    zeros = _vary_like(jnp.zeros((rows, kcap), jnp.float32), acc)
    idx_acc, val_acc = jax.lax.fori_loop(0, (n + pad) // chunk, cbody,
                                         (zeros, zeros))
    slot = jnp.arange(kcap)[None, :]
    idx = jnp.where(slot < cnt[:, None], idx_acc.astype(jnp.int32), n)
    return idx, val_acc, new_mem, cnt


# ---------------------------------------------------------------------------
# compressed-weight serving GEMMs (kernels/sparse_gemm.py)
# ---------------------------------------------------------------------------


def sparse_gemm_ref(x: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                    row_len: int) -> jnp.ndarray:
    """Densify-then-matmul oracle for ``sparse_gemm``.

    x: [M, row_len]; idx/val: [R, kcap] compact survivor buffers
    (row-local indices, out-of-row sentinel idx = row_len, val = 0).
    Decodes the [R, row_len] weight through the canonical scatter-add
    decoder semantics and contracts: ``y = x @ W.T`` in f32.
    """
    w = jnp.zeros((idx.shape[0], row_len), jnp.float32)
    w = jax.vmap(lambda o, i, v: o.at[i].add(v, mode="drop"))(
        w, idx, val.astype(jnp.float32))
    return x.astype(jnp.float32) @ w.T


def qdq_gemm_ref(x: jnp.ndarray, levels: jnp.ndarray,
                 scale: jnp.ndarray) -> jnp.ndarray:
    """Dequantize-then-matmul oracle for ``qdq_gemm``: per-row integer
    levels times the [R, 1] f32 scale, contracted in f32."""
    w = levels.astype(jnp.float32) * scale.astype(jnp.float32).reshape(-1, 1)
    return x.astype(jnp.float32) @ w.T


# ---------------------------------------------------------------------------
# flash attention (causal, optional sliding window), GQA
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, *, window: int = -1):
    """q: [B, S, H, D]; k, v: [B, S, KV, D].  Causal; window > 0 limits
    attention to the last ``window`` positions.  f32 accumulation."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, S, KV, G, D) * (D ** -0.5)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window > 0:
        mask &= pos[None, :] > pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def flash_decode_ref(q, k, v, valid):
    """Oracle for ``flash_decode_fwd``: single-token GQA attention over
    ring-cache contents under a precomputed slot-validity mask.

    q: [B, 1, H, D]; k, v: [B, C, KV, D]; valid: [C] bool."""
    B, _, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, 1, KV, G, D) * (D ** -0.5)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, 1, H, D).astype(q.dtype)


def paged_decode_ref(q, kp, vp, kscale, vscale, tables, lengths):
    """Gather-then-attend oracle for ``paged_decode_fwd``.

    q: [B, 1, H, D]; kp/vp: [n_pages, page_size, KV, D] pool arenas
    (fp, or int8 levels with the [n_pages, page_size] f32 per-token-slot
    scales); tables: [B, P] int32 block tables (-1 = unallocated);
    lengths: [B] int32.  Materializes each request's logical KV view
    through the table, dequantizes, and attends with explicit masked
    normalization — a length-0 row (free engine slot) yields exact
    zeros, matching the kernel, where ``jax.nn.softmax`` would emit a
    uniform distribution over garbage.
    """
    B, _, H, D = q.shape
    n_pages, ps, KV, _ = kp.shape
    P = tables.shape[1]
    G = H // KV
    tbl = jnp.clip(tables, 0, n_pages - 1)
    k = kp[tbl].astype(jnp.float32)                  # [B, P, ps, KV, D]
    v = vp[tbl].astype(jnp.float32)
    if kp.dtype == jnp.int8:
        k = k * kscale[tbl].astype(jnp.float32)[..., None, None]
        v = v * vscale[tbl].astype(jnp.float32)[..., None, None]
    k = k.reshape(B, P * ps, KV, D)
    v = v.reshape(B, P * ps, KV, D)
    live = jnp.arange(P * ps)[None, :] < lengths[:, None]        # [B, C]
    qf = q.astype(jnp.float32).reshape(B, 1, KV, G, D) * (D ** -0.5)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k)
    s = jnp.where(live[:, None, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(live[:, None, None, None, :], jnp.exp(s - m), 0.0)
    denom = jnp.moveaxis(jnp.sum(p, axis=-1), -1, 1)[..., None]  # [B,1,KV,G,1]
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v) / jnp.maximum(denom, 1e-30)
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# bucketed QSGD stochastic quantization
# ---------------------------------------------------------------------------


def qsgd_bucketed_ref(x: jnp.ndarray, u: jnp.ndarray, s: int):
    """x: [buckets, n]; u: uniform [buckets, n] in [0,1).  Per-bucket l2
    norm; levels xi stochastically rounded.  Returns quantized [buckets, n]."""
    xf = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(jnp.square(xf), axis=1, keepdims=True))
    safe = jnp.where(norm > 0, norm, 1.0)
    level = jnp.abs(xf) / safe * s
    low = jnp.floor(level)
    xi = low + (u < (level - low)).astype(jnp.float32)
    q = norm * jnp.sign(xf) * xi / s
    return jnp.where(norm > 0, q, jnp.zeros_like(xf))
