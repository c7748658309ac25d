"""Pallas TPU kernels for the compressed-weight serving forward
(DESIGN.md §11).

Two GEMM families, both consuming the storage layouts that
``serve/compressed.py`` builds from a trained checkpoint's persisted
PolicySpec:

  * :func:`sparse_gemm` — sparse-weight × dense-activation product over
    the compact ``(idx, val)`` survivor buffers of DESIGN.md §3.3
    (rows enumerate the *output* features, indices are row-local input
    coordinates, empty slots carry the ``idx = row_len, val = 0``
    sentinel).  Each grid program decodes one ``(block_rows, chunk)``
    weight tile from its survivor slots via the same chunked one-hot
    contraction the compact compressor uses — the tile lives only in
    VMEM registers, the dense weight never exists in HBM — and feeds it
    straight to the MXU against the resident activation block.

  * :func:`qdq_gemm` — QSGD-dequantize-fused product over per-row
    integer levels + f32 scales: ``y = x @ (levels * scale).T`` with the
    dequantize folded into the same VMEM residency as the matmul.

Both are tiled over (activation rows, weight rows); geometry
(``block_rows`` height of the weight tile, decode ``chunk``) is
autotunable (kernels/autotune.py) and changes timing only — outputs are
bit-identical across geometries.  Oracles live in ``kernels/ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.launch_stats import LAUNCHES

DEFAULT_BLOCK_M = 128


def _pad_dim(x: jnp.ndarray, axis: int, multiple: int,
             value=0) -> jnp.ndarray:
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# sparse (idx, val) GEMM
# ---------------------------------------------------------------------------


def _sparse_kernel(x_ref, idx_ref, val_ref, o_ref, *, chunk: int):
    """One (block_rows, block_m) tile of the *transposed* output.

    x_ref: [block_m, n] activations; idx_ref/val_ref: [block_rows, kcap]
    survivor buffers; o_ref: [block_rows, block_m] (weight rows on the
    sublanes, so an 8-row weight tile is a legal TPU block at any decode
    batch).  The weight tile is decoded chunk-by-chunk with a one-hot
    contraction (MXU-friendly, no scatter) and immediately contracted
    against the matching activation columns; sentinel slots (val = 0)
    contribute nothing.
    """
    n = x_ref.shape[1]
    bm = x_ref.shape[0]
    br = idx_ref.shape[0]
    idx = idx_ref[...]
    val = val_ref[...].astype(jnp.float32)

    def body(j, acc):
        base = pl.multiple_of(j * chunk, chunk)
        cols = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
        # [br, kcap, chunk] one-hot of each survivor against this chunk
        oh = (idx[:, :, None] == cols).astype(jnp.float32)
        # decode the (br, chunk) weight tile: w[r, c] = sum_s val[r,s]*oh
        # ([r, 1, s] @ [r, s, c], a batched matmul per weight row)
        w = jnp.einsum("rqs,rsc->rqc", val[:, None, :], oh,
                       preferred_element_type=jnp.float32)[:, 0, :]
        xc = x_ref[:, pl.ds(base, chunk)].astype(jnp.float32)
        return acc + jax.lax.dot_general(
            w, xc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n // chunk, body,
                            jnp.zeros((br, bm), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def sparse_gemm(x: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                row_len: int, *, block_m: int = DEFAULT_BLOCK_M,
                block_rows: int = 8, chunk: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """``y[m, r] = sum_s val[r, s] * x[m, idx[r, s]]``.

    x: [M, row_len] dense activations (any float dtype; f32 compute).
    idx/val: [R, kcap] compact survivor buffers (row-local indices,
    out-of-row sentinel ``idx = row_len, val = 0``).  Returns [M, R] f32.
    """
    M, n = x.shape
    R, kcap = idx.shape
    if n != row_len:
        raise ValueError(f"x has {n} features, buffers expect {row_len}")
    LAUNCHES["sparse_gemm"] += 1
    xp = _pad_dim(x.astype(jnp.float32), 1, chunk)
    xp = _pad_dim(xp, 0, min(block_m, max(M, 1)))
    bm = min(block_m, xp.shape[0])
    xp = _pad_dim(xp, 0, bm)
    br = min(block_rows, R)
    # sentinel-pad extra rows: idx = row_len never matches a real column
    # and val = 0 kills the padded-column match
    idxp = _pad_dim(idx, 0, br, value=row_len)
    valp = _pad_dim(val, 0, br)
    n_p = xp.shape[1]
    grid = (xp.shape[0] // bm, idxp.shape[0] // br)
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n_p), lambda m, r: (m, 0)),
            pl.BlockSpec((br, kcap), lambda m, r: (r, 0)),
            pl.BlockSpec((br, kcap), lambda m, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda m, r: (r, m)),
        out_shape=jax.ShapeDtypeStruct((idxp.shape[0], xp.shape[0]),
                                       jnp.float32),
        interpret=interpret,
        name="sparse_gemm",
    )(xp, idxp, valp)
    return out[:R, :M].T


# ---------------------------------------------------------------------------
# QSGD-dequantize-fused GEMM
# ---------------------------------------------------------------------------


def _qdq_kernel(x_ref, lv_ref, scale_ref, o_ref):
    """One (block_rows, block_m) tile of the transposed output:
    dequantize the integer weight tile in VMEM (levels * per-row scale)
    and contract."""
    w = lv_ref[...].astype(jnp.float32) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = jax.lax.dot_general(
        w, x_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def qdq_gemm(x: jnp.ndarray, levels: jnp.ndarray, scale: jnp.ndarray,
             *, block_m: int = DEFAULT_BLOCK_M, block_rows: int = 8,
             interpret: bool = False) -> jnp.ndarray:
    """``y = x @ (levels * scale).T`` with the dequantize fused.

    x: [M, n]; levels: [R, n] integer QSGD levels (sign * xi); scale:
    [R, 1] f32 per-row scale (||w_row|| / s).  Returns [M, R] f32.
    """
    M, n = x.shape
    R = levels.shape[0]
    if levels.shape[1] != n:
        raise ValueError(
            f"x has {n} features, levels rows have {levels.shape[1]}")
    LAUNCHES["qdq_gemm"] += 1
    xp = _pad_dim(x.astype(jnp.float32), 0, min(block_m, max(M, 1)))
    bm = min(block_m, xp.shape[0])
    xp = _pad_dim(xp, 0, bm)
    br = min(block_rows, R)
    lvp = _pad_dim(levels, 0, br)
    scp = _pad_dim(scale.astype(jnp.float32).reshape(R, 1), 0, br)
    grid = (xp.shape[0] // bm, lvp.shape[0] // br)
    out = pl.pallas_call(
        _qdq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda m, r: (m, 0)),
            pl.BlockSpec((br, n), lambda m, r: (r, 0)),
            pl.BlockSpec((br, 1), lambda m, r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda m, r: (r, m)),
        out_shape=jax.ShapeDtypeStruct((lvp.shape[0], xp.shape[0]),
                                       jnp.float32),
        interpret=interpret,
        name="qdq_gemm",
    )(xp, lvp, scp)
    return out[:R, :M].T
