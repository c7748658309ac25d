"""Operation and byte counts: the useful work of a step or a kernel,
computed from shapes, whatever implements it.

- :func:`kernel_bytes_moved`: the least HBM traffic of one compressor
  launch (copied from the repo's ``benchmarks/roofline.py``);
- :func:`train_flops_per_token`: model FLOPs of the forward and
  backward passes per trained token, attention included, recomputation
  not counted;
- :func:`compress_bytes`: a worker's compressor bytes per sync.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``device_kind`` ``kind``; a
    kind the table does not hold is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}; "
                       f"have {sorted(table)}")
    return table[kind]


def kernel_bytes_moved(kernel: str, rows: int, row_len: int, k: int,
                       kcap: int | None = None,
                       dtype_bytes: int = 4) -> float:
    """Least HBM traffic (bytes) of one fused compression-kernel launch.

    Each kernel streams its [rows, row_len] accumulator once and writes
    its outputs once:

    * ``topk_compress``: read acc, write (selected, new_memory) dense
      planes + a per-row count → 3 planes + 4·rows;
    * ``topk_compact``: read acc, write new_memory dense + the compact
      (idx, val) survivor buffers (kcap slots/row, idx int32 + val f32)
      + a per-row count → 2 planes + 2·kcap·rows·4 + 4·rows;
    * ``qsgd``: read (x, u), write quantized → 3 planes.
    """
    plane = float(rows) * row_len * dtype_bytes
    if kernel == "topk_compress":
        return 3 * plane + 4 * rows
    if kernel == "topk_compact":
        if kcap is None:
            raise ValueError("topk_compact bytes model needs kcap")
        return 2 * plane + rows * kcap * (4 + dtype_bytes) + 4 * rows
    if kernel == "qsgd":
        return 3 * plane
    raise ValueError(f"unknown kernel {kernel!r}")


def topk_fraction(policy: str) -> float:
    """k of a catch-all Top_k policy string, ``topk:k=<fraction>``."""
    m = re.fullmatch(r"topk:k=([0-9.eE+-]+)", policy.strip())
    if not m:
        raise ValueError(f"not a catch-all Top_k policy: {policy!r}")
    return float(m.group(1))


def matmul_params(dm: dict) -> int:
    """Weights that enter a matrix product per token (the embedding
    lookup is not one)."""
    d, H, KV, hd, ff, L, V = (dm[k] for k in ("d", "H", "KV", "hd", "ff",
                                              "L", "V"))
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    return L * layer + d * V


def params(dm: dict) -> int:
    d, L, V = dm["d"], dm["L"], dm["V"]
    return matmul_params(dm) + V * d + (2 * L + 1) * d


def train_flops_per_token(dm: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 3 x (2 x matmul weights +
    causal attention, whose QK^T and PV products cost 2 x 2 x H x hd
    per earlier position, (seq + 1) / 2 of them on average)."""
    fwd = 2 * matmul_params(dm) + dm["L"] * 2 * dm["H"] * dm["hd"] * (seq + 1)
    return 3.0 * fwd


def leaf_rows(dm: dict) -> list:
    """(rows, row_len) of every leaf's Top_k rows (hidden-axis rows)."""
    d, H, KV, hd, ff, L, V = (dm[k] for k in ("d", "H", "KV", "hd", "ff",
                                              "L", "V"))
    return [(V, d), (V, d), (1, d), (L, d), (L, d), (L * H * hd, d),
            (L * KV * hd, d), (L * KV * hd, d), (L * H * hd, d),
            (L * ff, d), (L * ff, d), (L * ff, d)]


def compress_bytes(dm: dict, traffic: dict) -> float:
    """One worker's compressor bytes per sync: every leaf's
    accumulator through the kernel of the cell's wire (dense Top_k
    planes for the dense psum, compact survivors for the allgather)."""
    frac = topk_fraction(traffic["policy"])
    kernel = ("topk_compact" if traffic["wire"] == "sparse_allgather"
              else "topk_compress")
    total = 0.0
    for rows, n in leaf_rows(dm):
        k = max(1, min(n, int(round(frac * n))))
        total += kernel_bytes_moved(kernel, rows, n, k, kcap=k)
    return total

