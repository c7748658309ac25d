"""Empirical block-geometry autotuner for the Pallas compression kernels.

The kernels in this package (``topk_compress``, ``topk_compact``,
``qsgd``) are row-independent: per-row threshold bisection and
quantization never read across block-row boundaries, so the grid
geometry — ``block_rows`` for all three, plus the scatter ``chunk`` for
the compact kernel — changes *timing only*, never outputs.  That makes
block geometry safely tunable: this module measures each candidate on
the live backend (warmup + ``block_until_ready``, best of N) and
records the winner in a per-device tuning table that
``kernels/dispatch.py`` resolves through transparently whenever a
``DispatchConfig`` leaves ``block_rows`` on auto (``None``).

Resolution order (DESIGN.md §10):

  1. an explicit ``DispatchConfig(block_rows=...)`` always wins;
  2. otherwise the tuning table, via an in-memory LRU keyed on the
     trace-time launch signature ``(kernel, dtype, rows, row_len, k,
     sign)`` — hit/miss counters surface in
     ``launch_stats.TUNE_CACHE``;
  3. untuned shapes fall back to the kernel's own geometry:
     ``topk_compress`` sizes its blocks from the rows it tiles
     (``topk_compress.dense_block_rows``), the other kernels take
     ``dispatch.DEFAULT_BLOCK_ROWS`` = 8 and chunk 128.

The table persists to ``artifacts/tuning/<device_kind>.json`` (one file
per accelerator kind; load/merge/save, so repeated tune runs extend the
table instead of clobbering it).  Corrupt, stale-schema or
foreign-device files never break dispatch: they load as an empty table
with a once-per-reason warning.  ``--retune`` (CLI and
``RunConfig.retune``) re-measures entries that already exist.

CLI (the CI tune-smoke lane)::

    PYTHONPATH=src python -m repro.kernels.autotune --smoke [--retune]

tunes a tiny fixed shape budget, prints one line per entry and a
``table: <path> (tuned N, cached M)`` summary — a second run reports
``tuned 0`` (every entry cache-hits).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time
import warnings
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import qsgd as _qsgd
from repro.kernels import sparse_gemm as _sgemm
from repro.kernels import topk_compress as _topk
from repro.kernels.launch_stats import TUNE_CACHE

TABLE_VERSION = 1
DEFAULT_TABLE_DIR = os.path.join("artifacts", "tuning")

#: the compact kernel's (block_rows, chunk, kcap) one-hot scatter
#: intermediate, at the historical default geometry (8, 128, max_cap)
VMEM_COMPACT_BYTES = 8 * 128 * (1 << 11) * 4

KERNELS = ("topk_compress", "topk_compact", "qsgd",
           "sparse_gemm", "qdq_gemm", "paged_decode")

#: fixed activation-row count for serving-GEMM measurement — the tuned
#: geometry tiles the *weight* rows; activation batch only scales every
#: candidate uniformly, so one representative M suffices
GEMM_MEASURE_M = 8

_LRU_MAX = 512
_lru: OrderedDict = OrderedDict()
_table: Optional[dict] = None   # lazily loaded persisted entries
_table_dir: str = DEFAULT_TABLE_DIR
_warned: set = set()


class TunedEntry(NamedTuple):
    """One tuning-table row: the winning geometry and its measured time."""

    block_rows: int
    chunk: Optional[int] = None   # topk_compact only
    us: float = float("nan")


class ShapeKey(NamedTuple):
    """A trace-time kernel launch signature — the tuning-table key."""

    kernel: str
    rows: int
    row_len: int
    k: int          # survivor count (Top_k family) or level count s (qsgd)
    sign: bool
    dtype: str = "f32"   # kernels compute in f32 today; keyed for later

    def as_str(self) -> str:
        return (f"{self.kernel}|{self.dtype}|{self.rows}|{self.row_len}"
                f"|{self.k}|{int(self.sign)}")


def _warn_once(tag: str, msg: str) -> None:
    if tag not in _warned:
        _warned.add(tag)
        warnings.warn(msg, stacklevel=3)


def device_kind() -> str:
    """Normalized accelerator kind — the per-device table filename."""
    kind = jax.devices()[0].device_kind
    return "".join(c if c.isalnum() else "_" for c in kind.lower())


def table_path(table_dir: Optional[str] = None) -> str:
    return os.path.join(table_dir or _table_dir, f"{device_kind()}.json")


def configure(table_dir: Optional[str] = None) -> None:
    """Point the module at a different table directory (tests, CLI) and
    drop the in-memory state so the next lookup reloads from it."""
    global _table_dir
    if table_dir is not None:
        _table_dir = table_dir
    clear_cache()


def clear_cache() -> None:
    """Drop the LRU, the loaded table and the warn-once registry (the
    persisted file is untouched)."""
    global _table
    _lru.clear()
    _table = None
    _warned.clear()


def _parse_key(s: str) -> Optional[ShapeKey]:
    parts = s.split("|")
    if len(parts) != 6 or parts[0] not in KERNELS:
        return None
    try:
        return ShapeKey(parts[0], int(parts[2]), int(parts[3]),
                        int(parts[4]), bool(int(parts[5])), parts[1])
    except ValueError:
        return None


def _valid_entry(key: ShapeKey, ent: dict) -> bool:
    br = ent.get("block_rows")
    if not isinstance(br, int) or br < 1:
        return False
    chunk = ent.get("chunk")
    if chunk is not None:
        if not isinstance(chunk, int) or chunk < 1:
            return False
        if key.row_len % chunk != 0:
            return False
    return True


def load_table(path: Optional[str] = None) -> dict:
    """Load a persisted tuning table → {key_str: TunedEntry}.

    Never raises on bad input: a missing file is an empty table; corrupt
    JSON, a stale schema version or a foreign-device file fall back to
    empty with a once-per-reason warning; malformed entries are skipped
    individually."""
    path = path or table_path()
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            raw = json.load(f)
    except (json.JSONDecodeError, OSError, UnicodeDecodeError):
        _warn_once(f"corrupt:{path}",
                   f"ignoring corrupt tuning table {path}")
        return {}
    if not isinstance(raw, dict) or raw.get("version") != TABLE_VERSION:
        _warn_once(f"stale:{path}",
                   f"ignoring stale tuning table {path} (version "
                   f"{raw.get('version') if isinstance(raw, dict) else '?'}, "
                   f"want {TABLE_VERSION})")
        return {}
    if raw.get("device_kind") != device_kind():
        _warn_once(f"foreign:{path}",
                   f"ignoring tuning table {path} tuned for device kind "
                   f"{raw.get('device_kind')!r} (this backend: "
                   f"{device_kind()!r})")
        return {}
    out = {}
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        _warn_once(f"stale:{path}",
                   f"ignoring tuning table {path}: no entries mapping")
        return {}
    for ks, ent in entries.items():
        key = _parse_key(ks)
        if key is None or not isinstance(ent, dict) \
                or not _valid_entry(key, ent):
            _warn_once(f"entry:{path}",
                       f"skipping malformed entries in tuning table {path}")
            continue
        out[ks] = TunedEntry(int(ent["block_rows"]),
                             ent.get("chunk"),
                             float(ent.get("us", float("nan"))))
    return out


def save_table(entries: dict, path: Optional[str] = None) -> str:
    """Merge ``entries`` ({key_str: TunedEntry}) into the on-disk table
    (new keys win) and write it back.  Returns the path written."""
    path = path or table_path()
    merged = dict(load_table(path))
    merged.update(entries)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    payload = {
        "version": TABLE_VERSION,
        "device_kind": device_kind(),
        "entries": {
            ks: {"block_rows": e.block_rows, "chunk": e.chunk, "us": e.us}
            for ks, e in sorted(merged.items())
        },
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def lookup(kernel: str, rows: int, row_len: int, k: int, sign: bool,
           dtype: str = "f32") -> Optional[TunedEntry]:
    """Trace-time table resolution: LRU first (``TUNE_CACHE['hit']``),
    then the lazily-loaded persisted table (``'miss'``; negative results
    are cached too, so untuned shapes cost one dict probe per trace)."""
    global _table
    ks = ShapeKey(kernel, rows, row_len, k, sign, dtype).as_str()
    if ks in _lru:
        _lru.move_to_end(ks)
        TUNE_CACHE["hit"] += 1
        return _lru[ks]
    TUNE_CACHE["miss"] += 1
    if _table is None:
        _table = load_table()
    ent = _table.get(ks)
    _lru[ks] = ent
    if len(_lru) > _LRU_MAX:
        _lru.popitem(last=False)
    return ent


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _time_us(fn, *args, iters: int = 3) -> float:
    """Best-of-N wall time in µs, after one warmup (compile) call; every
    call is ``block_until_ready`` so async dispatch can't undercount."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def block_row_candidates(rows: int, row_len: int) -> list:
    """Powers of two up to the row count (clamped) whose (c, row_len)
    f32 plane fits ``topk_compress.DENSE_PLANE_BYTES``, the envelope of
    the Top_k kernel's own rule (block_rows 8 at max_row 2^17)."""
    cands = set()
    p = 1
    while p < max(rows, 1):
        cands.add(p)
        p *= 2
    cands.add(rows)
    out = sorted(c for c in cands
                 if c * row_len * 4 <= _topk.DENSE_PLANE_BYTES)
    return out or [min(rows, 8)]


def chunk_candidates(row_len: int) -> list:
    out = [c for c in (128, 256, 512, 1024) if row_len % c == 0]
    return out or [row_len]


def page_block_candidates(pages: int) -> list:
    """Pages-per-block candidates for ``paged_decode``: powers of two up
    to the block-table width, plus the width itself (single-block)."""
    cands = set()
    p = 1
    while p < max(pages, 1):
        cands.add(p)
        p *= 2
    cands.add(max(pages, 1))
    return sorted(cands)


def _interpret_default(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def measure_entry(key: ShapeKey, *, iters: int = 3,
                  interpret: Optional[bool] = None) -> TunedEntry:
    """Measure every candidate geometry for one launch signature and
    return the winner."""
    interp = _interpret_default(interpret)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(key.rows, key.row_len).astype(np.float32))
    best: Optional[TunedEntry] = None
    if key.kernel == "topk_compress":
        for br in block_row_candidates(key.rows, key.row_len):
            fn = jax.jit(functools.partial(
                _topk.topk_compress, k=key.k, sign=key.sign,
                block_rows=br, interpret=interp))
            us = _time_us(fn, x, iters=iters)
            if best is None or us < best.us:
                best = TunedEntry(br, None, us)
    elif key.kernel == "qsgd":
        u = jnp.asarray(rng.rand(key.rows, key.row_len).astype(np.float32))
        for br in block_row_candidates(key.rows, key.row_len):
            fn = jax.jit(functools.partial(
                _qsgd.qsgd_quantize, s=key.k, block_rows=br,
                interpret=interp))
            us = _time_us(fn, x, u, iters=iters)
            if best is None or us < best.us:
                best = TunedEntry(br, None, us)
    elif key.kernel == "topk_compact":
        from repro.kernels.dispatch import capacity
        kcap = capacity(key.k, key.row_len)
        for br in block_row_candidates(key.rows, key.row_len):
            for chunk in chunk_candidates(key.row_len):
                if br * chunk * kcap * 4 > VMEM_COMPACT_BYTES:
                    continue
                fn = jax.jit(functools.partial(
                    _topk.topk_compact, k=key.k, kcap=kcap, sign=key.sign,
                    block_rows=br, chunk=chunk, interpret=interp))
                us = _time_us(fn, x, iters=iters)
                if best is None or us < best.us:
                    best = TunedEntry(br, chunk, us)
        if best is None:   # every pair over budget: keep the default
            best = TunedEntry(min(key.rows, 8), 128, float("nan"))
    elif key.kernel == "sparse_gemm":
        # key.k is the compact capacity kcap; rows/row_len describe the
        # weight in its serving orientation (rows = output features)
        xact = jnp.asarray(
            rng.randn(GEMM_MEASURE_M, key.row_len).astype(np.float32))
        idx = jnp.asarray(rng.randint(
            0, key.row_len, (key.rows, key.k)).astype(np.int32))
        val = jnp.asarray(rng.randn(key.rows, key.k).astype(np.float32))
        for br in block_row_candidates(key.rows, key.row_len):
            for chunk in chunk_candidates(key.row_len):
                if br * chunk * key.k * 4 > VMEM_COMPACT_BYTES:
                    continue
                fn = jax.jit(functools.partial(
                    _sgemm.sparse_gemm, row_len=key.row_len,
                    block_rows=br, chunk=chunk, interpret=interp))
                us = _time_us(fn, xact, idx, val, iters=iters)
                if best is None or us < best.us:
                    best = TunedEntry(br, chunk, us)
        if best is None:
            best = TunedEntry(min(key.rows, 8), 128, float("nan"))
    elif key.kernel == "qdq_gemm":
        xact = jnp.asarray(
            rng.randn(GEMM_MEASURE_M, key.row_len).astype(np.float32))
        levels = jnp.asarray(rng.randint(
            -key.k, key.k + 1, (key.rows, key.row_len)).astype(np.int8))
        scale = jnp.asarray(
            rng.rand(key.rows, 1).astype(np.float32))
        for br in block_row_candidates(key.rows, key.row_len):
            fn = jax.jit(functools.partial(
                _sgemm.qdq_gemm, block_rows=br, interpret=interp))
            us = _time_us(fn, xact, levels, scale, iters=iters)
            if best is None or us < best.us:
                best = TunedEntry(br, None, us)
    elif key.kernel == "paged_decode":
        # signature: rows = block-table width (max pages per request),
        # row_len = page size, k = head_dim, sign = int8 page layout;
        # block_rows stores the winning pages-per-block
        from repro.kernels import paged_attention as _pa
        P, ps, hd = key.rows, key.row_len, key.k
        B, KV, G = 4, 1, 8
        n_pages = B * P
        q = jnp.asarray(rng.randn(B, 1, KV * G, hd).astype(np.float32))
        if key.sign:
            kp = jnp.asarray(rng.randint(
                -127, 128, (n_pages, ps, KV, hd)).astype(np.int8))
            vp = jnp.asarray(rng.randint(
                -127, 128, (n_pages, ps, KV, hd)).astype(np.int8))
        else:
            kp = jnp.asarray(
                rng.randn(n_pages, ps, KV, hd).astype(np.float32))
            vp = jnp.asarray(
                rng.randn(n_pages, ps, KV, hd).astype(np.float32))
        ks_ = jnp.asarray(rng.rand(n_pages, ps).astype(np.float32))
        vs_ = jnp.asarray(rng.rand(n_pages, ps).astype(np.float32))
        tbl = jnp.asarray(
            np.arange(n_pages).reshape(B, P).astype(np.int32))
        lens = jnp.asarray(np.full(B, P * ps, np.int32))
        for pb in page_block_candidates(P):
            fn = jax.jit(functools.partial(
                _pa.paged_decode_fwd, pages_per_block=pb,
                interpret=interp))
            us = _time_us(fn, q, kp, vp, ks_, vs_, tbl, lens, iters=iters)
            if best is None or us < best.us:
                best = TunedEntry(pb, None, us)
    else:
        raise ValueError(f"unknown kernel {key.kernel!r}; "
                         f"expected one of {KERNELS}")
    return best


def tune(keys, *, iters: int = 3, retune: bool = False, save: bool = True,
         interpret: Optional[bool] = None, verbose: bool = False) -> dict:
    """Tune every ShapeKey in ``keys`` that isn't already in the table
    (all of them with ``retune``), persist the merged table, and return
    {key_str: TunedEntry} for the keys measured this call."""
    global _table
    if _table is None:
        _table = load_table()
    fresh = {}
    cached = 0
    for key in keys:
        ks = key.as_str() if isinstance(key, ShapeKey) else str(key)
        if not retune and ks in _table:
            cached += 1
            if verbose:
                print(f"  cached {ks} -> {_table[ks]}")
            continue
        ent = measure_entry(key, iters=iters, interpret=interpret)
        fresh[ks] = ent
        if verbose:
            print(f"  tuned  {ks} -> block_rows={ent.block_rows}"
                  + (f" chunk={ent.chunk}" if ent.chunk else "")
                  + f" ({ent.us:.1f} us)")
    if fresh:
        _table.update(fresh)
        if save:
            save_table(fresh)
        _lru.clear()   # resolutions cached before this tune are stale
    tune.last_cached = cached   # introspection for the CLI/tests
    return fresh


def tune_for_run(op_tree, params, cfg=None, *, downlink=None,
                 iters: int = 3, retune: bool = False,
                 compact: bool = False, verbose: bool = False) -> dict:
    """Tune exactly the launch signatures a training run's compression
    would dispatch (``dispatch.launch_plans`` over the uplink — and
    downlink — operator trees against the per-worker param shapes)."""
    from repro.kernels import dispatch as dsp
    keys = list(dsp.launch_plans(op_tree, params, cfg, compact=compact))
    if downlink is not None:
        for key in dsp.launch_plans(downlink, params, cfg, compact=compact):
            if key not in keys:
                keys.append(key)
    return tune(keys, iters=iters, retune=retune, verbose=verbose)


# ---------------------------------------------------------------------------
# CLI — the CI tune-smoke lane
# ---------------------------------------------------------------------------

#: tiny interpret-friendly budget: one signature per kernel family
SMOKE_KEYS = (
    ShapeKey("topk_compress", 4, 256, 8, False),
    ShapeKey("topk_compress", 1, 1024, 16, True),
    ShapeKey("topk_compact", 4, 256, 8, False),
    ShapeKey("qsgd", 1, 1024, 15, False),
    ShapeKey("sparse_gemm", 8, 256, 16, False),
    ShapeKey("qdq_gemm", 8, 256, 15, False),
    ShapeKey("paged_decode", 4, 16, 32, False),
    ShapeKey("paged_decode", 4, 16, 32, True),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="autotune Pallas compression-kernel block geometry")
    ap.add_argument("--smoke", action="store_true",
                    help="tune the tiny fixed smoke shape budget")
    ap.add_argument("--retune", action="store_true",
                    help="re-measure entries already in the table")
    ap.add_argument("--dir", default=None, help="tuning-table directory "
                    f"(default {DEFAULT_TABLE_DIR})")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--kernel", choices=KERNELS)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--row-len", type=int)
    ap.add_argument("--k", type=int)
    ap.add_argument("--sign", action="store_true")
    args = ap.parse_args(argv)
    if args.dir:
        configure(args.dir)
    if args.smoke:
        keys = list(SMOKE_KEYS)
    elif args.kernel:
        if not (args.rows and args.row_len and args.k):
            ap.error("--kernel needs --rows, --row-len and --k")
        keys = [ShapeKey(args.kernel, args.rows, args.row_len, args.k,
                         args.sign)]
    else:
        ap.error("pass --smoke or an explicit --kernel shape")
    fresh = tune(keys, iters=args.iters, retune=args.retune, verbose=True)
    path = table_path()
    print(f"table: {path} (tuned {len(fresh)}, cached {tune.last_cached})")
    return 0 if os.path.exists(path) or not fresh else 1


if __name__ == "__main__":
    raise SystemExit(main())
