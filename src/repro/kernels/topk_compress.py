"""Pallas TPU kernels: fused blockwise top-k compression + error update.

The paper's per-sync hot spot: compressing a ~25M-element accumulator
(m + x - x̂) with Top_k.  A GPU implementation radix-selects; on TPU we
instead run a **bisection threshold search** — 24 rounds of
compare-and-count, pure VPU (8x128 lanes) work with no sorting network
and no MXU involvement — then a masked select, the optional 1-bit
Sign quantization of the survivors (SignTop_k, Lemma 3), and the fused
error-memory update ``m' = acc - selected``, all in one VMEM residency
of the block.  See DESIGN.md §3 (hardware adaptation).

Two emission modes share the threshold search:

  * :func:`topk_compress` — *dense* survivors (zeros elsewhere), the
    input to a dense psum/pmean aggregation;
  * :func:`topk_compact` — *compact* ``(idx int32, val f32)`` survivor
    buffers of capacity ``kcap`` per row, written directly via an
    in-kernel prefix-sum compaction (cumsum of the survivor mask gives
    each survivor its output slot; a chunked one-hot matmul performs
    the slot scatter on the MXU — TPUs have no vector scatter).  This
    is the wire form of ``aggregate="sparse_allgather"`` and is sort-
    free (DESIGN.md §3.3).

Grid: one program per row-block.  Block shape (ROWS, n) where n is the
row length (the shard-local compression row, typically 1-8k), a
multiple of the 128 lanes.  Each block runs the bisection's chain of
dependent cross-lane reductions once, whatever its height, so
``topk_compress`` sizes ROWS from the shape it tiles
(:func:`dense_block_rows`): as tall as a VMEM budget allows, to hide
that chain behind more rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.launch_stats import BLOCK_ROWS, LAUNCHES

#: scoped VMEM the compression kernels may use (v5e has 128 MiB of
#: VMEM; the compiler's default scope is 16 MiB).  At block_rows 8 the
#: Top_k, compact and QSGD kernels compile for v5e up to 2^17-lane rows
#: within it — ``DispatchConfig.max_row``.
VMEM_LIMIT_BYTES = 64 << 20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
#: matmul precision of the one-hot slot scatters: f32 operands exact
EXACT = jax.lax.Precision.HIGHEST
#: VMEM of one (block_rows, n) f32 plane under :func:`dense_block_rows`:
#: that of the 8-row block at 2^17 lanes, so its three planes,
#: double-buffered, and its temporaries fit ``VMEM_LIMIT_BYTES`` at any
#: row length (8 MiB planes do not).  On v5e a 4 MiB plane ran the
#: kernel 10-12 % faster than a 2 MiB one at n = 4096 and 7168.
DENSE_PLANE_BYTES = 4 << 20


def dense_block_rows(rows: int, n: int) -> int:
    """Block height of a ``topk_compress`` launch over [rows, n].

    The largest power of two >= 8 that divides ``rows`` (so no launch
    pads) and keeps a (block, n) f32 plane inside
    ``DENSE_PLANE_BYTES``; 8 where none larger does (rows near
    ``DispatchConfig.max_row``, row counts with no larger power-of-two
    divisor); ``rows`` itself below 8 (global Top_k's single row).
    """
    if rows < 8:
        return rows
    br = 8
    while rows % (2 * br) == 0 and 2 * br * n * 4 <= DENSE_PLANE_BYTES:
        br *= 2
    return br


def out_struct(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """A kernel output typed like its input ``like``: inside a
    ``shard_map`` it varies over the same manual axes (row kernels are
    row-local), which ``check_vma`` requires of a ``pallas_call``."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _bisect_threshold(a: jnp.ndarray, k: int, iters: int) -> jnp.ndarray:
    """Per-row magnitude threshold keeping ~k entries of ``a`` (= |acc|).

    Maintains cnt(a >= lo) > k >= cnt(a >= hi); generically (distinct
    magnitudes, interval narrower than the k-th/k+1-th gap) the hi bound
    keeps exactly k.  If ties or the iteration budget leave
    cnt(a >= hi) < k, fall back to lo, which keeps >= k (a strictly
    better sparsifier).
    """
    hi = jnp.max(a, axis=1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((a >= mid).astype(jnp.int32), axis=1, keepdims=True)
        keep_hi = cnt > k
        lo = jnp.where(keep_hi, mid, lo)
        hi = jnp.where(keep_hi, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    c_hi = jnp.sum((a >= hi).astype(jnp.int32), axis=1, keepdims=True)
    return jnp.where(c_hi >= k, hi, lo)


def _kernel(acc_ref, sel_ref, mem_ref, cnt_ref, *, k: int, iters: int,
            sign: bool):
    acc = acc_ref[...].astype(jnp.float32)        # [ROWS, N]
    a = jnp.abs(acc)
    thr = _bisect_threshold(a, k, iters)
    # exact zeros are never survivors (zero-padded / all-zero rows must
    # not count toward the wire-bits ledger)
    mask = (a >= thr) & (a > 0.0)
    cnt = jnp.sum(mask.astype(jnp.int32), axis=1)
    sel = jnp.where(mask, acc, 0.0)
    if sign:
        norm = jnp.sqrt(jnp.sum(sel * sel, axis=1, keepdims=True))
        denom = jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
        sel = jnp.where(mask, jnp.sign(acc) * norm / denom, 0.0)
    sel_ref[...] = sel.astype(sel_ref.dtype)
    mem_ref[...] = (acc - sel).astype(mem_ref.dtype)
    cnt_ref[...] = cnt[:, None].astype(jnp.int32)


def topk_compress(acc: jax.Array, k: int, *, iters: int = 24,
                  sign: bool = False, block_rows: int | None = None,
                  interpret: bool = False):
    """acc: [rows, n] -> (selected [rows, n], new_mem [rows, n], cnt [rows]).

    ``block_rows=None`` sizes the blocks from ``acc``'s own shape
    (:func:`dense_block_rows`; inside a ``shard_map``, the shard-local
    rows), counted in ``launch_stats.BLOCK_ROWS`` as ``rule``.  VMEM per
    program: 3 blocks of (block_rows, n) f32, double-buffered, plus the
    kernel's temporaries — inside ``VMEM_LIMIT_BYTES`` up to n = 2^17
    at block_rows = 8 (``DispatchConfig.max_row``).
    """
    LAUNCHES["topk_compress"] += 1
    rows, n = acc.shape
    if block_rows is None:
        block_rows = dense_block_rows(rows, n)
        BLOCK_ROWS[("topk_compress", "rule", block_rows)] += 1
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        acc = jnp.pad(acc, ((0, pad), (0, 0)))
    grid = (acc.shape[0] // br,)
    kern = functools.partial(_kernel, k=k, iters=iters, sign=sign)
    sel, mem, cnt = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct(acc.shape, jnp.float32, acc),
            out_struct(acc.shape, jnp.float32, acc),
            out_struct((acc.shape[0], 1), jnp.int32, acc),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="topk_compress",
    )(acc)
    cnt = cnt[:, 0]
    if pad:
        sel, mem, cnt = sel[:rows], mem[:rows], cnt[:rows]
    return sel, mem, cnt


# ---------------------------------------------------------------------------
# compact emission (the sparse wire format)
# ---------------------------------------------------------------------------


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along the lanes (axis 1): log2(n) shifted
    adds over lane rotations — Mosaic lowers no ``cumsum``."""
    n = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0)
        shift *= 2
    return x


def _compact_kernel(acc_ref, idx_ref, val_ref, mem_ref, cnt_ref, pos_scr,
                    sel_scr, *, k: int, kcap: int, iters: int, sign: bool,
                    chunk: int):
    acc = acc_ref[...].astype(jnp.float32)        # [ROWS, N]
    rows, n = acc.shape
    a = jnp.abs(acc)
    thr = _bisect_threshold(a, k, iters)
    mask = (a >= thr) & (a > 0.0)
    # prefix-sum compaction: each survivor's output slot is the count of
    # survivors strictly before it in the row.  Survivors past the
    # buffer capacity (only possible under heavy ties) are dropped from
    # the wire; the fused memory update below absorbs them.
    pos = _prefix_sum(mask.astype(jnp.int32)) - 1
    emit = mask & (pos < kcap)
    cnt = jnp.sum(emit.astype(jnp.int32), axis=1)
    sel = jnp.where(emit, acc, 0.0)
    if sign:
        norm = jnp.sqrt(jnp.sum(sel * sel, axis=1, keepdims=True))
        denom = jnp.maximum(cnt[:, None].astype(jnp.float32), 1.0)
        sel = jnp.where(emit, jnp.sign(acc) * norm / denom, 0.0)
    mem_ref[...] = (acc - sel).astype(mem_ref.dtype)
    cnt_ref[...] = cnt[:, None].astype(jnp.int32)
    # slot scatter as a chunked one-hot matmul: TPUs have no vector
    # scatter, but onehot[r, c, j] = [pos == j & emit] contracted
    # against the values (and against the global indices) on the MXU
    # writes every chunk's survivors to their slots.  f32 holds indices
    # exactly up to 2^24 >> max_row, and the HIGHEST-precision product
    # keeps all 24 bits (the MXU's default rounds its operands to bf16:
    # an index above 256 or an f32 value would come out wrong).  The
    # chunks are read back from
    # VMEM scratch (a ref slices at a traced offset, a value does not);
    # slot -1 marks a coordinate that does not cross the wire.
    pos_scr[...] = jnp.where(emit, pos, -1)
    sel_scr[...] = sel
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, 1, kcap), 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def body(g, carry):
        idx_acc, val_acc = carry
        base = pl.multiple_of(g * chunk, chunk)
        p = pos_scr[:, pl.ds(base, chunk)]
        v = sel_scr[:, pl.ds(base, chunk)]
        oh = (p[:, :, None] == cols).astype(jnp.float32)
        gidx = jnp.broadcast_to((g * chunk + lane).astype(jnp.float32),
                                (rows, chunk))
        # [r, 1, c] @ [r, c, j]: a batched matmul per compression row
        val_acc = val_acc + jnp.einsum(
            "rqc,rcj->rqj", v[:, None, :], oh, precision=EXACT,
            preferred_element_type=jnp.float32)[:, 0, :]
        idx_acc = idx_acc + jnp.einsum(
            "rqc,rcj->rqj", gidx[:, None, :], oh, precision=EXACT,
            preferred_element_type=jnp.float32)[:, 0, :]
        return idx_acc, val_acc

    zeros = jnp.zeros((rows, kcap), jnp.float32)
    idx_acc, val_acc = jax.lax.fori_loop(0, n // chunk, body, (zeros, zeros))
    # empty slots carry the sentinel index n (one past the row): the
    # decoder's scatter-add drops out-of-bounds writes, so a gathered
    # buffer never needs its count to be decoded.
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, kcap), 1)
    idx_ref[...] = jnp.where(slot < cnt[:, None],
                             idx_acc.astype(jnp.int32), n)
    val_ref[...] = val_acc.astype(val_ref.dtype)


def topk_compact(acc: jax.Array, k: int, kcap: int, *, iters: int = 24,
                 sign: bool = False, block_rows: int = 8, chunk: int = 128,
                 interpret: bool = False):
    """Compact Top_k: [rows, n] -> (idx [rows, kcap] int32,
    val [rows, kcap] f32, new_mem [rows, n] f32, cnt [rows] int32).

    Survivor slots are filled in ascending index order; slots past
    ``cnt[r]`` hold ``(idx=n, val=0)`` — the out-of-row sentinel that a
    scatter-add decoder drops.  ``n`` must be a multiple of ``chunk``
    (the dispatch layer lane-aligns rows).  VMEM per program adds the
    (block_rows, chunk, kcap) one-hot to the dense-kernel budget —
    ~4 MB at (8, 128, 1024) f32, so dispatch caps kcap (``max_cap``).
    """
    LAUNCHES["topk_compact"] += 1
    rows, n = acc.shape
    if n % chunk:
        raise ValueError(f"row length {n} not a multiple of chunk {chunk}")
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        acc = jnp.pad(acc, ((0, pad), (0, 0)))
    grid = (acc.shape[0] // br,)
    kern = functools.partial(_compact_kernel, k=k, kcap=kcap, iters=iters,
                             sign=sign, chunk=chunk)
    scratch = [pltpu.VMEM((br, n), jnp.int32), pltpu.VMEM((br, n), jnp.float32)]
    idx, val, mem, cnt = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((br, n), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((br, kcap), lambda i: (i, 0)),
            pl.BlockSpec((br, kcap), lambda i: (i, 0)),
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct((acc.shape[0], kcap), jnp.int32, acc),
            out_struct((acc.shape[0], kcap), jnp.float32, acc),
            out_struct(acc.shape, jnp.float32, acc),
            out_struct((acc.shape[0], 1), jnp.int32, acc),
        ],
        scratch_shapes=scratch,
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="topk_compact",
    )(acc)
    cnt = cnt[:, 0]
    if pad:
        idx, val, mem, cnt = idx[:rows], val[:rows], mem[:rows], cnt[:rows]
    return idx, val, mem, cnt
