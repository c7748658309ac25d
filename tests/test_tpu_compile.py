"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached (``jax.experimental.topologies``), at the launch shapes the
yi-6b chip smoke (``chip_smoke.py``) produces.

Nothing runs: a compile that passes proves only that Mosaic accepts the
kernel — block tiling, VMEM budget — which interpret mode never checks.
Every kernel is called with ``interpret=False`` explicitly (the wrappers
would pick interpret mode off-TPU).  The topology is described inside a
module fixture, so only the worker that runs this file loads the TPU
compiler; where it cannot be described the tests skip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch as dsp
from repro.kernels import paged_attention as pa
from repro.kernels import qsgd
from repro.kernels import sparse_gemm as sg
from repro.kernels import topk_compress as tk

# yi-6b (configs/yi_6b.py): every compression row of the shard-local
# compressor is a d_model = 4096 row; k_frac 0.01 keeps k = 41 in a
# lane-aligned capacity of 128.  The embedding leaf [64000, 4096] gives
# the most rows of one launch.
D, FF, V, HD, KV, H = 4096, 11008, 64000, 128, 4, 32
K = 41
KCAP = dsp.capacity(K, D)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_topk_compress_compiles(one_chip):
    _compile(lambda a: tk.topk_compress(a, K, interpret=False), one_chip,
             ((V, D), jnp.float32))


@pytest.mark.parametrize("rows,n,k,height", [
    (20480, 7168, 72, 128),  # yi-34b's widest Top_k launch (w1, w3)
    (V, D, K, 256),          # yi-6b's embedding and head
    (8, 1 << 17, K, 8),      # the longest row dispatch admits
    (65536, 256, 3, 4096),   # a short row: its (rows, 1) vectors weigh most
])
def test_topk_compress_compiles_at_rule_height(one_chip, rows, n, k,
                                               height):
    """The height ``topk_compress`` picks from its shape fits v5e's
    scoped VMEM: three planes double-buffered plus the temporaries."""
    assert tk.dense_block_rows(rows, n) == height
    _compile(lambda a: tk.topk_compress(a, k, interpret=False), one_chip,
             ((rows, n), jnp.float32))


def test_topk_compact_compiles(one_chip):
    _compile(lambda a: tk.topk_compact(a, K, KCAP, interpret=False),
             one_chip, ((V, D), jnp.float32))


def test_qsgd_compiles_at_max_row(one_chip):
    """The longest row dispatch admits, in a packed 8-row megabuffer."""
    n = dsp.DispatchConfig().max_row
    _compile(lambda x, u: qsgd.qsgd_quantize(x, u, 15, interpret=False),
             one_chip, ((8, n), jnp.float32), ((8, n), jnp.float32))


@pytest.mark.parametrize("m", [8, 128])
def test_sparse_gemm_compiles(one_chip, m):
    """yi-6b's w1 ([4096, 11008]) stored as compact rows of W.T, against
    a decode batch of 8 and a 128-token prefill."""
    _compile(lambda x, i, v: sg.sparse_gemm(x, i, v, D, interpret=False),
             one_chip, ((m, D), jnp.bfloat16), ((FF, KCAP), jnp.int32),
             ((FF, KCAP), jnp.float32))


def test_qdq_gemm_compiles(one_chip):
    _compile(lambda x, lv, s: sg.qdq_gemm(x, lv, s, interpret=False),
             one_chip, ((8, D), jnp.bfloat16), ((FF, D), jnp.int8),
             ((FF, 1), jnp.float32))


@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.bfloat16])
def test_paged_decode_compiles(one_chip, kv_dtype):
    """Decode batch 8 at hd 128, 32-token pages, 8 pages per request."""
    B, P, ps = 8, 8, 32
    n_pages = B * P

    def fn(q, kp, vp, ks, vs, tbl, lens):
        return pa.paged_decode_fwd(q, kp, vp, ks, vs, tbl, lens,
                                   interpret=False)

    _compile(fn, one_chip, ((B, 1, H, HD), jnp.bfloat16),
             ((n_pages, ps, KV, HD), kv_dtype),
             ((n_pages, ps, KV, HD), kv_dtype),
             ((n_pages, ps), jnp.float32), ((n_pages, ps), jnp.float32),
             ((B, P), jnp.int32), ((B,), jnp.int32))


def test_flash_attention_prefill_compiles(one_chip):
    """The serving prefill (``--flash``): one 128-token prompt at
    yi-6b's 32/4 GQA heads."""
    from repro.kernels import flash_attention as fa
    S = 128
    _compile(lambda q, k, v: fa.flash_attention_fwd(q, k, v,
                                                    interpret=False),
             one_chip, ((1, S, H, HD), jnp.bfloat16),
             ((1, S, KV, HD), jnp.bfloat16), ((1, S, KV, HD), jnp.bfloat16))
