"""Tests for the kernel block-geometry autotuner (kernels/autotune.py,
DESIGN.md §10).

The load-bearing invariants:

* resolution order — explicit ``DispatchConfig(block_rows=...)`` beats
  the tuning table beats the kernel's own geometry (``topk_compress``'s
  shape rule, the fixed default of the other kernels);
* robustness — corrupt, stale-schema or foreign-device table files
  load as empty with a once-per-reason warning, never an exception;
* the geometry-transparency contract the whole feature rests on: the
  kernels are row-independent, so ANY tuned geometry produces
  bit-for-bit the default geometry's outputs (pinned across TUNE_GRID).
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at
from repro.kernels import dispatch as dsp
from repro.kernels import qsgd as qk
from repro.kernels.launch_stats import (
    BLOCK_ROWS, TUNE_CACHE, reset_tune_cache,
)
from tests.strategies import TUNE_GRID

KCFG = dict(mode="kernel")   # force the kernel path (interpret on CPU)


@pytest.fixture(autouse=True)
def isolated_table(tmp_path):
    """Point the autotuner at a throwaway table dir for every test and
    restore the default afterwards."""
    at.configure(str(tmp_path))
    reset_tune_cache()
    yield
    at.configure(at.DEFAULT_TABLE_DIR)


def _entry(br, chunk=None, us=12.5):
    return at.TunedEntry(br, chunk, us)


def test_table_roundtrip():
    k1 = at.ShapeKey("topk_compress", 4, 512, 16, False)
    k2 = at.ShapeKey("topk_compact", 2, 256, 8, True)
    path = at.save_table({k1.as_str(): _entry(4)})
    assert at.load_table(path)[k1.as_str()].block_rows == 4
    # second save merges instead of clobbering
    at.save_table({k2.as_str(): _entry(2, chunk=128)})
    loaded = at.load_table(path)
    assert set(loaded) == {k1.as_str(), k2.as_str()}
    assert loaded[k2.as_str()].chunk == 128
    # the persisted table feeds lookup after a cache drop
    at.clear_cache()
    ent = at.lookup("topk_compact", 2, 256, 8, True)
    assert ent == loaded[k2.as_str()]


def test_missing_table_is_empty_and_silent():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert at.load_table() == {}
        assert at.lookup("topk_compress", 1, 256, 8, False) is None


@pytest.mark.parametrize("payload,reason", [
    ("{not json", "corrupt"),
    (json.dumps({"version": 999, "entries": {}}), "stale"),
    (json.dumps([1, 2, 3]), "stale"),
    (None, "foreign"),   # filled in below with a wrong device_kind
])
def test_bad_tables_load_safe(payload, reason):
    path = at.table_path()
    import os
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if payload is None:
        payload = json.dumps({
            "version": at.TABLE_VERSION, "device_kind": "tpu_v9000",
            "entries": {"topk_compress|f32|1|256|8|0": {"block_rows": 2}},
        })
    with open(path, "w") as f:
        f.write(payload)
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        assert at.load_table() == {}
        assert at.load_table() == {}    # warn-once: no second warning
    assert len(wlog) == 1, [str(w.message) for w in wlog]
    assert reason in str(wlog[0].message) or "ignoring" in str(
        wlog[0].message)
    # dispatch still resolves (to the default) instead of raising
    assert at.lookup("topk_compress", 1, 256, 8, False) is None


def test_malformed_entries_skipped_individually():
    good = at.ShapeKey("topk_compress", 4, 512, 16, False)
    path = at.table_path()
    import os
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({
            "version": at.TABLE_VERSION, "device_kind": at.device_kind(),
            "entries": {
                good.as_str(): {"block_rows": 4, "chunk": None, "us": 1.0},
                "nonsense-key": {"block_rows": 4},
                "topk_compress|f32|1|256|8|0": {"block_rows": "eight"},
                # chunk must divide row_len
                "topk_compact|f32|1|256|8|0": {"block_rows": 1,
                                               "chunk": 100},
            },
        }, f)
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        loaded = at.load_table()
    assert set(loaded) == {good.as_str()}
    assert len(wlog) == 1   # once per file, not per entry


def test_lookup_lru_counters():
    key = at.ShapeKey("topk_compress", 4, 512, 16, False)
    at.save_table({key.as_str(): _entry(4)})
    at.clear_cache()
    reset_tune_cache()
    assert at.lookup(*key[:5]).block_rows == 4
    assert TUNE_CACHE == {"hit": 0, "miss": 1}
    assert at.lookup(*key[:5]).block_rows == 4
    assert TUNE_CACHE == {"hit": 1, "miss": 1}
    # negative result is cached too: one miss, then hits
    assert at.lookup("qsgd", 1, 256, 7, False) is None
    assert at.lookup("qsgd", 1, 256, 7, False) is None
    assert TUNE_CACHE == {"hit": 2, "miss": 2}


def test_resolution_order():
    key = at.ShapeKey("topk_compress", 4, 512, 16, False)
    at.save_table({key.as_str(): _entry(2)})
    at.clear_cache()
    dsp.reset_launches()
    tuned = dsp.DispatchConfig(**KCFG)                 # auto: table wins
    explicit = dsp.DispatchConfig(block_rows=3, **KCFG)
    assert dsp._block_rows(tuned, *key[:5]) == 2
    assert dsp._block_rows(explicit, *key[:5]) == 3    # explicit beats table
    assert BLOCK_ROWS == {("topk_compress", "table", 2): 1,
                          ("topk_compress", "explicit", 3): 1}
    # an untuned Top_k shape is left to the kernel's rule, which sizes
    # its blocks from the rows it tiles; the compact kernel and qsgd
    # keep the fixed fallback
    assert dsp._block_rows(tuned, "topk_compress", 9, 640, 5,
                           False) is None
    assert dsp._block_rows(tuned, "qsgd", 9, 640, 5,
                           False) == dsp.DEFAULT_BLOCK_ROWS
    assert dsp._compact_geometry(tuned, 9, 640, 5, False) == (
        dsp.DEFAULT_BLOCK_ROWS, dsp.DEFAULT_CHUNK)


def _synthetic_geometry(kernel, rows, row_len):
    """A deliberately non-default (but valid) geometry per signature."""
    br = max(1, min(rows, 3))
    chunk = None
    if kernel == "topk_compact":
        chunk = 256 if row_len % 256 == 0 else 128
    return br, chunk


@pytest.mark.parametrize("kernel,rows,row_len,k,sign", TUNE_GRID)
def test_tuned_equals_untuned_bit_for_bit(kernel, rows, row_len, k, sign):
    """The contract that makes geometry tunable at all: block_rows /
    chunk change timing only — outputs are bit-for-bit identical for
    any table entry, across the whole signature grid."""
    br, chunk = _synthetic_geometry(kernel, rows, row_len)
    key = at.ShapeKey(kernel, rows, row_len, k, sign)
    at.save_table({key.as_str(): _entry(br, chunk)})
    at.clear_cache()
    tuned = dsp.DispatchConfig(**KCFG)
    default = dsp.DispatchConfig(block_rows=dsp.DEFAULT_BLOCK_ROWS, **KCFG)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(rows, row_len).astype(np.float32))
    if kernel == "topk_compress":
        out_t = dsp.topk_rows(x, k, sign=sign, cfg=tuned)
        out_d = dsp.topk_rows(x, k, sign=sign, cfg=default)
    elif kernel == "topk_compact":
        kcap = dsp.capacity(k, row_len)
        out_t = dsp.compact_rows(x, k, kcap, sign=sign, cfg=tuned)
        out_d = dsp.compact_rows(x, k, kcap, sign=sign, cfg=default)
    else:   # qsgd — geometry resolved through the same table
        u = jnp.asarray(rng.rand(rows, row_len).astype(np.float32))
        ent = at.lookup(*key[:5])
        assert ent is not None and ent.block_rows == br
        out_t = qk.qsgd_quantize(x, u, k, block_rows=ent.block_rows,
                                 interpret=True)
        out_d = qk.qsgd_quantize(x, u, k,
                                 block_rows=dsp.DEFAULT_BLOCK_ROWS,
                                 interpret=True)
    for a, b in zip(jax.tree_util.tree_leaves(out_t),
                    jax.tree_util.tree_leaves(out_d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tune_measures_and_caches():
    key = at.ShapeKey("topk_compress", 2, 256, 8, False)
    fresh = at.tune([key], iters=1, interpret=True)
    assert key.as_str() in fresh
    assert fresh[key.as_str()].block_rows in (1, 2)
    assert np.isfinite(fresh[key.as_str()].us)
    import os
    assert os.path.exists(at.table_path())
    # second run: everything cache-hits, nothing re-measured
    again = at.tune([key], iters=1, interpret=True)
    assert again == {} and at.tune.last_cached == 1
    # retune forces a re-measure
    forced = at.tune([key], iters=1, retune=True, interpret=True)
    assert key.as_str() in forced


def test_tune_for_run_covers_launch_plans():
    from repro.core import policy as pol
    params = {"w": jnp.zeros((256, 128)), "b": jnp.zeros((128,))}
    up, down = pol.as_channel_spec("topk:k=0.05").resolve(params)
    cfg = dsp.DispatchConfig(**KCFG)
    want = {k.as_str() for k in dsp.launch_plans(up, params, cfg)}
    assert want, "grid premise: the policy must dispatch kernels"
    fresh = at.tune_for_run(up, params, cfg, iters=1)
    assert set(fresh) == want
    # the table now feeds dispatch for exactly those signatures
    at.clear_cache()
    for ks in want:
        key = at._parse_key(ks)
        assert at.lookup(*key[:5]) is not None


def test_cli_smoke_twice(capsys):
    assert at.main(["--smoke", "--iters", "1"]) == 0
    out1 = capsys.readouterr().out
    assert f"tuned {len(at.SMOKE_KEYS)}" in out1
    import os
    assert os.path.exists(at.table_path())
    assert at.main(["--smoke", "--iters", "1"]) == 0
    out2 = capsys.readouterr().out
    assert f"tuned 0, cached {len(at.SMOKE_KEYS)}" in out2
