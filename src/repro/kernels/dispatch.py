"""Operator → Pallas-kernel dispatch for the unified Qsparse engine.

The engine (core/engine.py) compresses the error-compensated
accumulator ``m + x - x̂`` once per sync round; on production shapes
that is the per-round hot spot.  This module maps ``CompressionOp``
instances to the fused Pallas kernels when shape/dtype/platform allow,
and falls back *transparently* to the dense reference operators in
``core/operators.py`` otherwise — same dense output, same wire-bit
accounting, so callers never see which path ran (except through
:func:`would_dispatch`, used by tests and benchmarks).

Three entry layers (see DESIGN.md §3.2-§3.4):

  * :func:`compress_tree` / :func:`channel_compress_tree` — the
    engine's per-round entries.  With ``pack=True`` (default)
    same-operator leaves are packed into one padded ``[rows, n]``
    megabuffer per (row length, k, sign) bucket — lane-aligned,
    zero-padded — so a whole pytree costs **one kernel launch per
    operator family** instead of one per leaf.  The kernels are
    row-independent, so packing is output-identical to the leaf-by-leaf
    path.  The channel form (uplink *and* downlink, DESIGN.md §5)
    additionally returns the updated error memory — fused from the
    kernel for Top_k leaves, ``acc − q`` elsewhere.
  * :func:`compress_leaf` / :func:`compact_compress` — per-leaf dense /
    compact form.  The compact form returns ``(idx, val)`` survivor
    buffers plus the fused error memory (the sparse wire format of
    ``aggregate="sparse_allgather"``, DESIGN.md §3.3).
  * :func:`topk_rows` / :func:`compact_rows` — raw pre-shaped row
    entries for the distributed shard compressor.

Dispatch rules (see DESIGN.md §3.2):

  ========================  =======================================
  operator                  kernel
  ========================  =======================================
  ``TopK``                  ``topk_compress`` on a single padded row
  ``RowTopK``               ``topk_compress``, one row per block-row
  ``SignSparsifier`` (top,  ``topk_compress(sign=True)`` single row
  m=2)
  ``RowSignTopK`` (m=2)     ``topk_compress(sign=True)`` per row
  ``QSGDQuantizer``         ``qsgd`` single bucket, external uniforms
  ========================  =======================================

The Top_k family additionally supports the compact emission mode
(``topk_compact``) with the scatter-free jnp oracle as its transparent
reference fallback.  Everything else (RandK, Sign, k-level, the
composed quantized sparsifiers, SignTopK with the L1 scale) runs the
reference operator.

Eligibility (``mode="auto"``): the backend is TPU (off-TPU the kernels
only exist in interpret mode, which is for validation, not speed), the
leaf has at least ``min_size`` elements, rows are lane-aligned (128)
and a row fits the VMEM budget (``max_row``).  ``mode="kernel"``
forces the kernel path (interpret off-TPU) for parity tests and
benchmarks; ``mode="reference"`` disables dispatch entirely.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bits as bitlib
from repro.core.operators import (
    CompressionOp,
    QSGDQuantizer,
    RowSignTopK,
    RowTopK,
    SignSparsifier,
    TopK,
    ops_for_leaves,
    resolve_k,
)
from repro.kernels import qsgd as _qsgd
from repro.kernels import sparse_gemm as _sgemm
from repro.kernels import topk_compress as _topk
from repro.kernels.launch_stats import (  # noqa: F401 — re-exported
    BLOCK_ROWS, LAUNCHES, reset_launches, total_launches,
)

LANES = 128  # TPU vector lane width: kernel rows are padded to this

#: the grid height of a ``qsgd``, ``topk_compact`` or serving-GEMM
#: launch whose shape has no tuning-table entry while ``block_rows`` is
#: on auto (None); ``topk_compress`` sizes its own
#: (``topk_compress.dense_block_rows``)
DEFAULT_BLOCK_ROWS = 8
DEFAULT_CHUNK = 128


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Where and when compression runs through the Pallas kernels.

    mode: "auto"      — kernels on TPU, references elsewhere (default)
          "kernel"    — force the kernel path (interpret mode off-TPU);
                        bypasses min_size but not structural limits
          "reference" — never dispatch (pure core/operators.py)
    min_size: smallest leaf (elements) worth a kernel launch in "auto"
    max_row:  longest kernel row (elements); bounds VMEM residency —
              at block_rows 8 the Top_k, compact and QSGD kernels
              compile for v5e inside ``topk_compress.VMEM_LIMIT_BYTES``
              up to 2^17 (tests/test_tpu_compile.py)
    max_cap:  largest compact survivor capacity (elements per row) the
              compact kernel accepts; bounds the (block_rows, chunk,
              kcap) one-hot intermediate of the slot scatter
    block_rows: grid block height handed to the kernels.  ``None``
          (default) resolves per launch signature through the autotune
          table (kernels/autotune.py; LRU → persisted per-device
          table); an untuned shape then runs ``topk_compress`` at the
          height its own rows allow (``topk_compress.dense_block_rows``)
          and the other kernels at ``DEFAULT_BLOCK_ROWS``.  An explicit
          int always wins over the table.  The kernels are
          row-independent, so the choice changes timing only — outputs
          are bit-identical.
    pack: megabuffer-pack same-bucket leaves in compress_tree (one
          kernel launch per operator family per sync round)
    interpret: None — auto (interpret off-TPU); bool to force
    row_axes: mesh axes the caller's ``shard_map`` region leaves Auto;
          :func:`topk_rows` and :func:`compact_rows` split their rows
          over them (``_split_rows``).  Empty outside a mesh region.
    """

    mode: str = "auto"
    min_size: int = 1 << 16
    max_row: int = 1 << 17
    max_cap: int = 1 << 11
    block_rows: Optional[int] = None
    pack: bool = True
    interpret: Optional[bool] = None
    row_axes: tuple = ()

    def __post_init__(self):
        if self.mode not in ("auto", "kernel", "reference"):
            raise ValueError(f"unknown dispatch mode {self.mode!r}")

    def kernels_enabled(self) -> bool:
        if self.mode == "reference":
            return False
        if self.mode == "kernel":
            return True
        return jax.default_backend() == "tpu"

    def _interpret(self) -> bool:
        if self.interpret is not None:
            return self.interpret
        return jax.default_backend() != "tpu"


DEFAULT = DispatchConfig()


def _resolve(cfg: Optional[DispatchConfig]) -> DispatchConfig:
    return cfg if cfg is not None else DEFAULT


def _block_rows(cfg: DispatchConfig, kernel: str, rows: int, row_len: int,
                k: int, sign: bool) -> Optional[int]:
    """Resolve one launch's grid height: an explicit
    ``cfg.block_rows`` wins, then the autotune table (hit/miss counters
    in ``launch_stats.TUNE_CACHE``).  Where neither applies,
    ``topk_compress`` gets ``None`` (the kernel sizes its blocks from
    the rows it tiles) and other kernels ``DEFAULT_BLOCK_ROWS``.  A
    ``topk_compress`` height set here is counted in ``BLOCK_ROWS``."""
    if cfg.block_rows is not None:
        br, how = cfg.block_rows, "explicit"
    else:
        from repro.kernels import autotune
        ent = autotune.lookup(kernel, rows, row_len, k, sign)
        if ent is None:
            return None if kernel == "topk_compress" else DEFAULT_BLOCK_ROWS
        br, how = ent.block_rows, "table"
    if kernel == "topk_compress":
        BLOCK_ROWS[(kernel, how, br)] += 1
    return br


def _compact_geometry(cfg: DispatchConfig, rows: int, row_len: int,
                      k: int, sign: bool) -> tuple[int, int]:
    """(block_rows, chunk) for a ``topk_compact`` launch — same
    resolution order; an explicit ``block_rows=`` pins the chunk to the
    default too (geometry is tuned as a pair)."""
    if cfg.block_rows is not None:
        return cfg.block_rows, DEFAULT_CHUNK
    from repro.kernels import autotune
    ent = autotune.lookup("topk_compact", rows, row_len, k, sign)
    if ent is not None:
        return ent.block_rows, ent.chunk or DEFAULT_CHUNK
    return DEFAULT_BLOCK_ROWS, DEFAULT_CHUNK


# ---------------------------------------------------------------------------
# shape plumbing
# ---------------------------------------------------------------------------


def _pad_to(flat: jnp.ndarray, multiple: int) -> jnp.ndarray:
    pad = (-flat.shape[0]) % multiple
    return jnp.pad(flat, (0, pad)) if pad else flat


def _as_single_row(x: jnp.ndarray) -> jnp.ndarray:
    """Flatten + zero-pad to a lane-aligned [1, n] row.  Zero padding is
    select-safe: |0| never beats a real survivor, and a zero survivor
    contributes zero to the dense output either way."""
    flat = _pad_to(x.reshape(-1).astype(jnp.float32), LANES)
    return flat[None, :]


def _as_rows(x: jnp.ndarray, row_len: int) -> jnp.ndarray:
    flat = _pad_to(x.reshape(-1).astype(jnp.float32), row_len)
    return flat.reshape(-1, row_len)


def _restore(out2d: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return out2d.reshape(-1)[: x.size].reshape(x.shape).astype(x.dtype)


def _padded_len(d: int, multiple: int) -> int:
    return d + ((-d) % multiple)


def capacity(k: int, n: int) -> int:
    """Lane-aligned compact survivor-buffer capacity for (k, row n)."""
    return min(_padded_len(max(k, 1), LANES), _padded_len(n, LANES))


# ---------------------------------------------------------------------------
# kernel rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelRule:
    """One operator-family → kernel mapping."""

    name: str
    matches: Callable[[CompressionOp], bool]
    eligible: Callable[[CompressionOp, tuple, "DispatchConfig"], bool]
    run: Callable  # (op, key, x, cfg) -> (dense_out, wire_bits)


def _size(shape: tuple) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def _global_row_ok(shape, cfg) -> bool:
    return _padded_len(_size(shape), LANES) <= cfg.max_row


def _row_len_of(op, shape) -> int:
    return min(op.row_len, _size(shape))


def _rows_ok(op, shape, cfg) -> bool:
    row = _row_len_of(op, shape)
    return row % LANES == 0 and row <= cfg.max_row


TOPK_FAMILY = ("topk_global", "row_topk", "signtopk_global", "row_signtopk")


def _plan_topk(rule_name: str, op, x):
    """Per-leaf Top_k-family launch plan: the pre-shaped [rows, n] f32
    buffer, static k, sign flag, and the counted-bits ledger closure.
    Shared by the per-leaf runners, megabuffer packing, and the compact
    emission path, so every route charges identical bits."""
    d = x.size
    if rule_name in ("topk_global", "signtopk_global"):
        sign = rule_name == "signtopk_global"
        k = resolve_k(op.k, d)
        rows = _as_single_row(x)
        if sign:
            bits_of = lambda c: bitlib.bits_signtopk_counted(d, c)
        else:
            bits_of = lambda c: bitlib.bits_topk_counted(d, c, op.value_bits)
    else:
        sign = rule_name == "row_signtopk"
        row = _row_len_of(op, x.shape)
        k = resolve_k(op.k, row)
        rows = _as_rows(x, row)
        nrows = rows.shape[0]
        # one 32-bit length/scale field per compression row; the counted
        # helpers already include one, hence the -32
        if sign:
            bits_of = lambda c: (jnp.float32(32 * nrows)
                                 + bitlib.bits_signtopk_counted(row, c)
                                 - jnp.float32(32))
        else:
            bits_of = lambda c: (jnp.float32(32 * nrows)
                                 + bitlib.bits_topk_counted(
                                     row, c, op.value_bits)
                                 - jnp.float32(32))
    return rows, k, sign, bits_of


def _run_topk_family(rule_name: str, op, key, x, cfg):
    rows, k, sign, bits_of = _plan_topk(rule_name, op, x)
    br = _block_rows(cfg, "topk_compress", rows.shape[0], rows.shape[1],
                     k, sign)
    sel, _mem, cnt = _topk.topk_compress(
        rows, k, sign=sign, block_rows=br, interpret=cfg._interpret())
    return _restore(sel, x), bits_of(jnp.sum(cnt))


def _run_qsgd(op: QSGDQuantizer, key, x, cfg):
    d = x.size
    flat = x.reshape(-1).astype(jnp.float32)
    # uniforms drawn exactly like the reference operator (same key, same
    # flat shape) keep the stochastic rounding bit-identical
    u = jax.random.uniform(key, flat.shape)
    row = _pad_to(flat, LANES)[None, :]
    br = _block_rows(cfg, "qsgd", 1, row.shape[1], op.s, False)
    out = _qsgd.qsgd_quantize(
        row, _pad_to(u, LANES)[None, :], op.s,
        block_rows=br, interpret=cfg._interpret())
    out = _restore(out, x)
    nz = jnp.sum(out != 0.0)
    return out, bitlib.bits_qsgd(d, op.s, nz)


RULES: tuple[KernelRule, ...] = (
    KernelRule(
        "topk_global",
        lambda op: type(op) is TopK,
        lambda op, shape, cfg: _global_row_ok(shape, cfg),
        functools.partial(_run_topk_family, "topk_global"),
    ),
    KernelRule(
        "row_topk",
        lambda op: type(op) is RowTopK,
        lambda op, shape, cfg: _rows_ok(op, shape, cfg),
        functools.partial(_run_topk_family, "row_topk"),
    ),
    KernelRule(
        "signtopk_global",
        lambda op: (type(op) is SignSparsifier and op.sparsifier == "top"
                    and op.m == 2),
        lambda op, shape, cfg: _global_row_ok(shape, cfg),
        functools.partial(_run_topk_family, "signtopk_global"),
    ),
    KernelRule(
        "row_signtopk",
        lambda op: type(op) is RowSignTopK and op.m == 2,
        lambda op, shape, cfg: _rows_ok(op, shape, cfg),
        functools.partial(_run_topk_family, "row_signtopk"),
    ),
    KernelRule(
        "qsgd_global",
        lambda op: type(op) is QSGDQuantizer,
        lambda op, shape, cfg: _global_row_ok(shape, cfg),
        _run_qsgd,
    ),
)


def select_rule(op: CompressionOp, shape: tuple,
                dtype=jnp.float32,
                cfg: Optional[DispatchConfig] = None) -> Optional[KernelRule]:
    """The kernel rule that would serve this (op, leaf), or None."""
    cfg = _resolve(cfg)
    if not cfg.kernels_enabled():
        return None
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return None
    if cfg.mode == "auto" and _size(shape) < cfg.min_size:
        return None
    for rule in RULES:
        if rule.matches(op) and rule.eligible(op, shape, cfg):
            return rule
    return None


def would_dispatch(op: CompressionOp, shape: tuple, dtype=jnp.float32,
                   cfg: Optional[DispatchConfig] = None) -> bool:
    """Introspection probe: True iff compress_leaf would use a kernel."""
    return select_rule(op, shape, dtype, cfg) is not None


# ---------------------------------------------------------------------------
# raw row-kernel entries (shard-local compressors in core/distributed.py)
# ---------------------------------------------------------------------------


def rows_eligible(row_len: int, cfg: Optional[DispatchConfig] = None,
                  leaf_size: Optional[int] = None) -> bool:
    """Can [rows, row_len] blocks go through the Top_k kernel?

    Mirrors select_rule's auto-mode policy: pass ``leaf_size`` so tiny
    leaves (below min_size) stay on the reference path instead of
    paying a kernel launch; mode="kernel" bypasses the floor.
    """
    cfg = _resolve(cfg)
    if not (cfg.kernels_enabled() and row_len % LANES == 0
            and row_len <= cfg.max_row):
        return False
    if (cfg.mode == "auto" and leaf_size is not None
            and leaf_size < cfg.min_size):
        return False
    return True


def compact_rows_eligible(row_len: int, kcap: int,
                          cfg: Optional[DispatchConfig] = None,
                          leaf_size: Optional[int] = None) -> bool:
    """Can [rows, row_len] blocks go through the *compact* kernel?
    The dense row policy plus the survivor-capacity VMEM bound."""
    cfg = _resolve(cfg)
    return rows_eligible(row_len, cfg, leaf_size) and kcap <= cfg.max_cap


def _split_rows(kernel: Callable, rows: jnp.ndarray, axes: tuple, interpret):
    """Run a row-independent Pallas kernel, ``kernel(rows,
    interpret=...)``, over ``rows`` [R, n].

    ``axes`` (``DispatchConfig.row_axes``) names the mesh axes that the
    calling ``shard_map`` region leaves Auto: the mesh engine's 'model'
    axis inside its worker-manual region.  XLA cannot partition a
    Mosaic call, so the rows are split over those axes by a nested
    ``shard_map`` on the calling region's mesh (Corollary 1's piecewise
    compression, shard by shard), zero-padded to a multiple of their
    size.  Every output of ``kernel`` leads with the row axis.

    The TPU compiler refuses the nested region when the program is
    traced under a ``jax.set_mesh`` context (jax then lowers it against
    the context's all-Auto mesh), so the mesh engine is traced without
    one: its shard_maps name their mesh.
    """
    if not axes:
        return kernel(rows, interpret=interpret)
    mesh = jax.sharding.get_abstract_mesh()
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    r = rows.shape[0]
    pad = (-r) % n_shards
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    spec = jax.sharding.PartitionSpec(axes)
    # interpret mode replays the kernel as untyped jnp: the engine then
    # leaves its regions unchecked too (core/distributed._checks_vma)
    out = jax.shard_map(functools.partial(kernel, interpret=interpret),
                        mesh=mesh, in_specs=spec, out_specs=spec,
                        axis_names=set(axes), check_vma=not interpret)(rows)
    return jax.tree_util.tree_map(lambda o: o[:r], out)


def topk_rows(rows: jnp.ndarray, k: int, *, sign: bool = False,
              cfg: Optional[DispatchConfig] = None):
    """Kernel Top_k/SignTop_k over pre-shaped [rows, n] blocks.

    Returns (selected, new_memory, count_per_row) — the fused kernel
    outputs.  Callers are responsible for :func:`rows_eligible`.
    """
    cfg = _resolve(cfg)
    br = _block_rows(cfg, "topk_compress", rows.shape[0], rows.shape[1],
                     k, sign)
    kernel = functools.partial(_topk.topk_compress, k=k, sign=sign,
                               block_rows=br)
    return _split_rows(kernel, rows, cfg.row_axes, cfg._interpret())


def compact_rows(rows: jnp.ndarray, k: int, kcap: int, *,
                 sign: bool = False,
                 cfg: Optional[DispatchConfig] = None,
                 leaf_size: Optional[int] = None):
    """Compact Top_k/SignTop_k over pre-shaped [rows, n] blocks.

    Kernel when :func:`compact_rows_eligible`, else the scatter-free
    jnp oracle (``ref.topk_compact_ref``) — identical outputs either
    way, and both forms are sort-free (they trace without
    ``lax.top_k``).

    Returns (idx [rows, kcap] int32, val [rows, kcap] f32,
    new_mem [rows, n] f32, cnt [rows] int32); empty slots carry the
    out-of-row sentinel (idx = n, val = 0) — see DESIGN.md §3.3.
    """
    cfg = _resolve(cfg)
    n = rows.shape[1]
    if compact_rows_eligible(n, kcap, cfg, leaf_size=leaf_size):
        br, chunk = _compact_geometry(cfg, rows.shape[0], n, k, sign)
        return _split_rows(functools.partial(
            _topk.topk_compact, k=k, kcap=kcap, sign=sign, block_rows=br,
            chunk=chunk), rows, cfg.row_axes, cfg._interpret())
    from repro.kernels.ref import topk_compact_ref
    return topk_compact_ref(rows.astype(jnp.float32), k, kcap, sign=sign)


# ---------------------------------------------------------------------------
# compact leaf compression (the sparse wire format)
# ---------------------------------------------------------------------------


class CompactLeaf(NamedTuple):
    """One leaf in compact wire form (DESIGN.md §3.3).

    idx/val are [rows, kcap] survivor buffers (rows = 1 for the global
    operators); slot j of row r holds the j-th surviving coordinate of
    that compression row in ascending index order, indices row-local.
    Slots past the row's survivor count hold (idx = row_len, val = 0) —
    the out-of-row sentinel a scatter-add decoder drops, so fixed-size
    buffers allgather without a decoded length.  ``mem`` is the fused
    error memory (leaf shape, f32) and ``bits`` the counted wire cost.
    """

    idx: jnp.ndarray
    val: jnp.ndarray
    mem: jnp.ndarray
    bits: jnp.ndarray
    row_len: int
    kcap: int


def decode_rows(idx: jnp.ndarray, val: jnp.ndarray,
                row_len: int) -> jnp.ndarray:
    """THE compact-buffer decoder: per-row scatter-add of [rows, kcap]
    (idx, val) into dense [rows, row_len] f32.  Out-of-row sentinel
    indices (empty slots, §3.3) drop; every consumer of the wire format
    decodes through here so the convention lives in one place."""
    out = jnp.zeros((idx.shape[0], row_len), jnp.float32)
    return jax.vmap(lambda o, i, v: o.at[i].add(v, mode="drop"))(
        out, idx, val)


def densify_compact(leaf: CompactLeaf, shape, dtype=jnp.float32):
    """Dense decode of a CompactLeaf: scatter-add rows, unpad, reshape
    to the original leaf shape."""
    out = decode_rows(leaf.idx, leaf.val, leaf.row_len)
    return out.reshape(-1)[: _size(tuple(shape))].reshape(shape).astype(dtype)


def would_compact(op: CompressionOp, shape: tuple, dtype=jnp.float32,
                  cfg: Optional[DispatchConfig] = None) -> bool:
    """True iff compact_compress would use the compact *kernel* (the
    fallback oracle produces the same wire form either way)."""
    cfg = _resolve(cfg)
    rule = next((r for r in RULES
                 if r.name in TOPK_FAMILY and r.matches(op)), None)
    if rule is None or not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return False
    d = _size(shape)
    if rule.name in ("topk_global", "signtopk_global"):
        n = _padded_len(d, LANES)
        k = resolve_k(op.k, d)
    else:
        n = _row_len_of(op, shape)
        k = resolve_k(op.k, n)
    return compact_rows_eligible(n, capacity(k, n), cfg, leaf_size=d)


def compact_compress(op: CompressionOp, key, x: jnp.ndarray,
                     cfg: Optional[DispatchConfig] = None
                     ) -> tuple[CompactLeaf, bool]:
    """Compact-form counterpart of :func:`compress_leaf` for the Top_k
    family (TopK / SignTopK(m=2) / RowTopK / RowSignTopK).

    Returns (CompactLeaf, used_kernel).  The fallback is the
    scatter-free reference oracle, not a dense compress: callers always
    get the compact wire form.  Ops outside the family raise TypeError
    (they have no sparse wire format — use compress_leaf).
    """
    cfg = _resolve(cfg)
    rule = next((r for r in RULES
                 if r.name in TOPK_FAMILY and r.matches(op)), None)
    if rule is None:
        raise TypeError(
            f"{type(op).__name__} has no compact wire form; "
            "compact_compress serves the Top_k family only")
    rows, k, sign, bits_of = _plan_topk(rule.name, op, x)
    n = rows.shape[1]
    kcap = capacity(k, n)
    # route on would_compact so the probe and the execution agree
    # (its dtype guard included — compact_rows alone never sees x.dtype)
    used = would_compact(op, x.shape, x.dtype, cfg)
    if used:
        br, chunk = _compact_geometry(cfg, rows.shape[0], n, k, sign)
        idx, val, mem, cnt = _topk.topk_compact(
            rows, k, kcap, sign=sign, block_rows=br, chunk=chunk,
            interpret=cfg._interpret())
    else:
        from repro.kernels.ref import topk_compact_ref
        idx, val, mem, cnt = topk_compact_ref(rows, k, kcap, sign=sign)
    mem_leaf = mem.reshape(-1)[: x.size].reshape(x.shape)
    bits = jnp.asarray(bits_of(jnp.sum(cnt)), jnp.float32)
    return CompactLeaf(idx, val, mem_leaf, bits, n, kcap), used


# ---------------------------------------------------------------------------
# compressed-weight serving GEMMs (DESIGN.md §11)
# ---------------------------------------------------------------------------


def _gemm_geometry(cfg: DispatchConfig, kernel: str, rows: int,
                   row_len: int, k: int) -> tuple[int, int]:
    """(block_rows, chunk) for a serving-GEMM launch — the same
    resolution order as the compression kernels: explicit
    ``cfg.block_rows``, then the autotune table, then the defaults."""
    if cfg.block_rows is not None:
        return cfg.block_rows, DEFAULT_CHUNK
    from repro.kernels import autotune
    ent = autotune.lookup(kernel, rows, row_len, k, False)
    if ent is not None:
        return ent.block_rows, ent.chunk or DEFAULT_CHUNK
    return DEFAULT_BLOCK_ROWS, DEFAULT_CHUNK


def paged_geometry(cfg: Optional[DispatchConfig], pages: int,
                   page_size: int, head_dim: int, quant: bool) -> int:
    """pages-per-block for a ``paged_decode`` launch — same resolution
    order as the GEMMs: explicit ``cfg.block_rows`` (reused as the page
    count per grid step), then the autotune table (signature = table
    width × page size × head_dim, sign bit = int8 pages), then the
    default; always clamped to the table width."""
    cfg = _resolve(cfg)
    if cfg.block_rows is not None:
        return max(1, min(cfg.block_rows, pages))
    from repro.kernels import autotune
    from repro.kernels.paged_attention import DEFAULT_PAGES_PER_BLOCK
    ent = autotune.lookup("paged_decode", pages, page_size, head_dim, quant)
    pb = ent.block_rows if ent is not None else DEFAULT_PAGES_PER_BLOCK
    return max(1, min(pb, pages))


def paged_decode(x: jnp.ndarray, kp: jnp.ndarray, vp: jnp.ndarray,
                 kscale: jnp.ndarray, vscale: jnp.ndarray,
                 tables: jnp.ndarray, lengths: jnp.ndarray,
                 cfg: Optional[DispatchConfig] = None) -> jnp.ndarray:
    """Serving entry for paged flash-decode attention.

    x: [B, 1, H, hd] rope'd queries; kp/vp/kscale/vscale: the KV page
    pool (see ``kernels/paged_attention.py``); tables: [B, P] block
    tables; lengths: [B].  Kernel when ``cfg.kernels_enabled()`` (pages
    gathered into VMEM via scalar-prefetch block tables, int8 dequant
    fused into the attention dot), the gather oracle otherwise;
    [B, 1, H, hd] either way.
    """
    cfg = _resolve(cfg)
    if cfg.kernels_enabled():
        pb = paged_geometry(cfg, tables.shape[-1], kp.shape[-3],
                            kp.shape[-1], kp.dtype == jnp.int8)
        from repro.kernels import paged_attention as _pa
        return _pa.paged_decode_fwd(x, kp, vp, kscale, vscale, tables,
                                    lengths, pages_per_block=pb,
                                    interpret=cfg._interpret())
    from repro.kernels.ref import paged_decode_ref
    return paged_decode_ref(x, kp, vp, kscale, vscale, tables, lengths)


def sparse_gemm(x: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                row_len: int, cfg: Optional[DispatchConfig] = None
                ) -> jnp.ndarray:
    """Serving entry for the sparse-weight × dense-activation GEMM.

    x: [M, row_len] activations; idx/val: [R, kcap] compact survivor
    buffers (rows enumerate output features).  Kernel when
    ``cfg.kernels_enabled()`` (the weight tile is decoded block-by-block
    in VMEM — the dense weight never exists in HBM), the
    densify-then-matmul oracle otherwise; [M, R] f32 either way.
    """
    cfg = _resolve(cfg)
    if cfg.kernels_enabled():
        br, chunk = _gemm_geometry(cfg, "sparse_gemm", idx.shape[0],
                                   row_len, idx.shape[1])
        return _sgemm.sparse_gemm(x, idx, val, row_len, block_rows=br,
                                  chunk=chunk, interpret=cfg._interpret())
    from repro.kernels.ref import sparse_gemm_ref
    return sparse_gemm_ref(x, idx, val, row_len)


def qdq_gemm(x: jnp.ndarray, levels: jnp.ndarray, scale: jnp.ndarray,
             cfg: Optional[DispatchConfig] = None) -> jnp.ndarray:
    """Serving entry for the QSGD-dequantize-fused GEMM.

    x: [M, n]; levels: [R, n] integer levels; scale: [R, 1] f32 per-row
    scales.  Kernel (dequantize fused into the matmul's VMEM residency)
    or the dequantize-then-matmul oracle; [M, R] f32 either way.
    """
    cfg = _resolve(cfg)
    if cfg.kernels_enabled():
        br, _ = _gemm_geometry(cfg, "qdq_gemm", levels.shape[0],
                               levels.shape[1], 0)
        return _sgemm.qdq_gemm(x, levels, scale, block_rows=br,
                               interpret=cfg._interpret())
    from repro.kernels.ref import qdq_gemm_ref
    return qdq_gemm_ref(x, levels, scale)


# ---------------------------------------------------------------------------
# public compression entry points (engine-facing)
# ---------------------------------------------------------------------------


def compress_leaf(op: CompressionOp, key, x: jnp.ndarray,
                  cfg: Optional[DispatchConfig] = None):
    """Compress one leaf: (dense_out, wire_bits, used_kernel).

    Kernel path when a rule matches and the leaf is eligible; otherwise
    the reference operator — identical output contract either way.
    """
    cfg = _resolve(cfg)
    rule = select_rule(op, x.shape, x.dtype, cfg)
    if rule is None:
        out, bits = op(key, x)
        return out, jnp.asarray(bits, jnp.float32), False
    out, bits = rule.run(op, key, x, cfg)
    return out, jnp.asarray(bits, jnp.float32), True


def _compress_leaves_packed(ops, keys, leaves, cfg, want_mem: bool = False):
    """Megabuffer-packed leaf compression (DESIGN.md §3.4).

    Kernel-eligible leaves are bucketed by launch signature —
    (row length, k, sign) for the Top_k family, (row length, s) for
    QSGD — and each bucket's pre-shaped rows are concatenated into one
    padded megabuffer for a single kernel launch.  The kernels are
    row-independent, so per-leaf outputs, error memories and counted
    bits are identical to the leaf-by-leaf path; only the launch count
    changes (one per populated bucket instead of one per leaf).

    With ``want_mem`` (the channel path, :func:`channel_compress_tree`)
    the third return carries per-leaf error memories: the kernel's
    *fused* ``acc − selected`` for Top_k-family leaves (no extra
    subtract outside the kernel), None for leaves whose memory the
    caller derives as ``acc − out``.
    """
    n = len(leaves)
    outs: list = [None] * n
    bit_terms: list = [None] * n
    mems: list = [None] * n
    topk_buckets: dict = {}
    qsgd_buckets: dict = {}
    for i, (op, key, x) in enumerate(zip(ops, keys, leaves)):
        rule = select_rule(op, x.shape, x.dtype, cfg)
        if rule is None:
            out, bits = op(key, x)
            outs[i] = out
            bit_terms[i] = jnp.asarray(bits, jnp.float32)
        elif rule.name == "qsgd_global":
            flat = x.reshape(-1).astype(jnp.float32)
            u = jax.random.uniform(key, flat.shape)
            row = _pad_to(flat, LANES)[None, :]
            urow = _pad_to(u, LANES)[None, :]
            qsgd_buckets.setdefault((row.shape[1], op.s), []).append(
                (i, row, urow, op, x))
        else:
            rows, k, sign, bits_of = _plan_topk(rule.name, op, x)
            topk_buckets.setdefault((rows.shape[1], k, sign), []).append(
                (i, rows, bits_of, x))
    for (_, k, sign), entries in topk_buckets.items():
        mega = (entries[0][1] if len(entries) == 1
                else jnp.concatenate([e[1] for e in entries], axis=0))
        br = _block_rows(cfg, "topk_compress", mega.shape[0], mega.shape[1],
                         k, sign)
        sel, mem, cnt = _topk.topk_compress(
            mega, k, sign=sign, block_rows=br,
            interpret=cfg._interpret())
        off = 0
        for i, rows, bits_of, x in entries:
            r = rows.shape[0]
            outs[i] = _restore(sel[off:off + r], x)
            if want_mem:
                mems[i] = _restore(mem[off:off + r], x)
            bit_terms[i] = jnp.asarray(
                bits_of(jnp.sum(cnt[off:off + r])), jnp.float32)
            off += r
    for (_, s), entries in qsgd_buckets.items():
        mega = (entries[0][1] if len(entries) == 1
                else jnp.concatenate([e[1] for e in entries], axis=0))
        megau = (entries[0][2] if len(entries) == 1
                 else jnp.concatenate([e[2] for e in entries], axis=0))
        br = _block_rows(cfg, "qsgd", mega.shape[0], mega.shape[1], s, False)
        out = _qsgd.qsgd_quantize(mega, megau, s, block_rows=br,
                                  interpret=cfg._interpret())
        for off, (i, _row, _urow, op, x) in enumerate(entries):
            o = _restore(out[off:off + 1], x)
            outs[i] = o
            bit_terms[i] = jnp.asarray(
                bitlib.bits_qsgd(x.size, op.s, jnp.sum(o != 0.0)),
                jnp.float32)
    return outs, bit_terms, mems


def compress_tree(op_tree, key, grads,
                  cfg: Optional[DispatchConfig] = None):
    """Kernel-aware counterpart of ``operators.compress_tree``: same
    operator-broadcast, key-splitting and bits-summing semantics, with
    each leaf routed through the kernels (megabuffer-packed per
    operator family when ``cfg.pack``) or :func:`compress_leaf`."""
    cfg = _resolve(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    ops = ops_for_leaves(op_tree, len(leaves))
    if key is not None:
        keys = jax.random.split(key, len(leaves))
    else:
        keys = [None] * len(leaves)
    if cfg.pack and cfg.kernels_enabled():
        outs, bit_terms, _ = _compress_leaves_packed(ops, keys, leaves, cfg)
    else:
        outs, bit_terms = [], []
        for op, k, g in zip(ops, keys, leaves):
            o, b, _ = compress_leaf(op, k, g, cfg)
            outs.append(o)
            bit_terms.append(b)
    total = jnp.sum(jnp.stack(bit_terms)) if bit_terms else jnp.float32(0)
    return jax.tree_util.tree_unflatten(treedef, outs), total


def channel_compress_tree(op_tree, key, acc,
                          cfg: Optional[DispatchConfig] = None,
                          *, want_leaf_bits: bool = False):
    """Channel-aware tree compression (DESIGN.md §5): compress the
    error-compensated accumulator ``acc`` and hand back the updated
    error memory alongside.

    Returns ``(q_tree, mem_tree, total_bits)`` with the invariant
    ``q + mem == acc`` per leaf.  Uplink and downlink both enter here
    (``core.channel.Channel.apply``), so downlink leaves join the same
    megabuffer packing buckets and trace-time launch counters as the
    uplink — one kernel launch per operator family per direction per
    sync round.  Top_k-family kernel leaves return the kernel's *fused*
    error memory (computed in the same VMEM residency, §3.3); every
    other leaf derives it as ``acc − q`` — bit-identical either way,
    both are the same f32 elementwise subtract.

    ``want_leaf_bits``: additionally return the per-leaf wire bits (a
    list of f32 scalars in flatten order — the per-leaf ledger of
    DESIGN.md §6) as a fourth element.  The total is always the sum of
    that list, so the aggregate ledger is unchanged either way.
    """
    cfg = _resolve(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(acc)
    ops = ops_for_leaves(op_tree, len(leaves))
    if key is not None:
        keys = jax.random.split(key, len(leaves))
    else:
        keys = [None] * len(leaves)
    if cfg.pack and cfg.kernels_enabled():
        outs, bit_terms, mems = _compress_leaves_packed(
            ops, keys, leaves, cfg, want_mem=True)
    else:
        outs, bit_terms, mems = [], [], []
        for op, k, g in zip(ops, keys, leaves):
            o, b, _ = compress_leaf(op, k, g, cfg)
            outs.append(o)
            bit_terms.append(b)
            mems.append(None)
    mems = [m if m is not None else a - o
            for m, a, o in zip(mems, leaves, outs)]
    total = jnp.sum(jnp.stack(bit_terms)) if bit_terms else jnp.float32(0)
    out = (jax.tree_util.tree_unflatten(treedef, outs),
           jax.tree_util.tree_unflatten(treedef, mems),
           total)
    if want_leaf_bits:
        return out + (list(bit_terms),)
    return out


# ---------------------------------------------------------------------------
# launch-plan introspection (the autotuner's work list)
# ---------------------------------------------------------------------------


def _plan_topk_shape(rule_name: str, op, shape) -> tuple[int, int, int, bool]:
    """Shape-only twin of :func:`_plan_topk`: (rows, row_len, k, sign)
    of the pre-shaped kernel buffer, without building arrays."""
    d = _size(shape)
    if rule_name in ("topk_global", "signtopk_global"):
        return (1, _padded_len(d, LANES), resolve_k(op.k, d),
                rule_name == "signtopk_global")
    row = _row_len_of(op, shape)
    return (_padded_len(d, row) // row, row, resolve_k(op.k, row),
            rule_name == "row_signtopk")


def launch_plans(op_tree, tree, cfg: Optional[DispatchConfig] = None,
                 *, compact: bool = False) -> list:
    """The static kernel-launch signatures :func:`compress_tree` /
    :func:`channel_compress_tree` would dispatch for this (op_tree,
    params-like tree) — mirroring the megabuffer bucketing under
    ``cfg.pack`` — as ``autotune.ShapeKey`` rows.  This is exactly the
    autotuner's work list (``autotune.tune_for_run``): tune these keys
    and every launch of the run resolves through the table.

    ``compact=True`` maps Top_k-family plans onto the compact-emission
    kernel (``topk_compact``) instead — the sparse-allgather wire of
    the distributed engine."""
    from repro.kernels.autotune import ShapeKey
    cfg = _resolve(cfg)
    plans: list = []
    if not cfg.kernels_enabled():
        return plans
    leaves = jax.tree_util.tree_leaves(tree)
    ops = ops_for_leaves(op_tree, len(leaves))
    topk_name = "topk_compact" if compact else "topk_compress"
    topk_buckets: dict = {}
    qsgd_buckets: dict = {}
    for op, x in zip(ops, leaves):
        rule = select_rule(op, x.shape, x.dtype, cfg)
        if rule is None:
            continue
        if rule.name == "qsgd_global":
            n = _padded_len(_size(x.shape), LANES)
            qsgd_buckets[(n, op.s)] = qsgd_buckets.get((n, op.s), 0) + 1
        else:
            rows, n, k, sign = _plan_topk_shape(rule.name, op, x.shape)
            topk_buckets[(n, k, sign)] = (
                topk_buckets.get((n, k, sign), 0) + rows)
            if not cfg.pack:
                key = ShapeKey(topk_name, rows, n, k, sign)
                if key not in plans:
                    plans.append(key)
    if cfg.pack:
        for (n, k, sign), rows in topk_buckets.items():
            plans.append(ShapeKey(topk_name, rows, n, k, sign))
        for (n, s), rows in qsgd_buckets.items():
            plans.append(ShapeKey("qsgd", rows, n, s, False))
    else:
        for (n, s), count in qsgd_buckets.items():
            key = ShapeKey("qsgd", 1, n, s, False)
            if key not in plans:
                plans.append(key)
    return plans
