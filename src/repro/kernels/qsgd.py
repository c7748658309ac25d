"""Pallas TPU kernel: bucketed QSGD stochastic quantization.

Standard production QSGD buckets the vector (norm per bucket) so the
kernel is single-pass: each program loads one row-block, computes the
per-row (bucket) l2 norm, stochastically rounds |x|/norm into s levels
using externally supplied uniform randoms (keeps the oracle bit-exact
and the kernel deterministic given its operands), and writes the
dequantized values.

Block shape (ROWS, bucket) — VPU elementwise + one row reduction; no MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.launch_stats import LAUNCHES
from repro.kernels.topk_compress import COMPILER_PARAMS


def _kernel(x_ref, u_ref, o_ref, *, s: int):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))
    safe = jnp.where(norm > 0, norm, 1.0)
    level = jnp.abs(x) / safe * s
    low = jnp.floor(level)
    xi = low + (u < (level - low)).astype(jnp.float32)
    q = norm * jnp.sign(x) * xi / s
    o_ref[...] = jnp.where(norm > 0, q, 0.0).astype(o_ref.dtype)


def qsgd_quantize(x: jax.Array, u: jax.Array, s: int, *,
                  block_rows: int = 8, interpret: bool = False):
    """x, u: [buckets, n] -> dequantized [buckets, n] (f32)."""
    LAUNCHES["qsgd"] += 1
    rows, n = x.shape
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        u = jnp.pad(u, ((0, pad), (0, 0)))
    grid = (x.shape[0] // br,)
    out = pl.pallas_call(
        functools.partial(_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, n), lambda i: (i, 0)),
            pl.BlockSpec((br, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((br, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="qsgd",
    )(x, u)
    return out[:rows]
