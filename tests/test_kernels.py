"""Pallas kernel correctness sweeps (interpret=True on CPU) against the
pure-jnp oracles in kernels/ref.py — shapes and dtypes swept."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,n,k", [
    (4, 256, 16), (7, 512, 50), (16, 1024, 10), (1, 128, 4), (9, 384, 100),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sign", [False, True])
def test_topk_compress_matches_ref(rows, n, k, dtype, sign):
    acc = jax.random.normal(jax.random.PRNGKey(rows * n), (rows, n)) \
        .astype(dtype)
    sel, mem, cnt = ops.topk_compress(acc, k, sign=sign)
    rsel, rmem, rcnt = ref.topk_compress_ref(acc.astype(jnp.float32), k,
                                             sign=sign)
    np.testing.assert_allclose(np.asarray(sel), np.asarray(rsel),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mem), np.asarray(rmem),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(cnt) == np.asarray(rcnt)).all()


@pytest.mark.parametrize("rows,n,k,kcap", [
    (4, 256, 16, 128), (8, 512, 50, 128), (1, 1024, 10, 128),
    (16, 384, 100, 128), (5, 640, 200, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sign", [False, True])
def test_topk_compact_matches_ref(rows, n, k, kcap, dtype, sign):
    acc = jax.random.normal(jax.random.PRNGKey(rows + n), (rows, n)) \
        .astype(dtype)
    idx, val, mem, cnt = ops.topk_compact(acc, k, kcap, sign=sign)
    ridx, rval, rmem, rcnt = ref.topk_compact_ref(
        acc.astype(jnp.float32), k, kcap, sign=sign)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(val), np.asarray(rval),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mem), np.asarray(rmem),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    # densify identity: scatter-add(compact) + memory == input (sentinel
    # slots drop out of bounds)
    dense = jax.vmap(lambda o, i, v: o.at[i].add(v, mode="drop"))(
        jnp.zeros((rows, n)), idx, val)
    np.testing.assert_allclose(np.asarray(dense + mem),
                               np.asarray(acc, np.float32),
                               rtol=1e-5, atol=1e-5)


def _dot_precisions(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dot_precisions(sub, out)
    return out


@pytest.mark.parametrize("which", ["kernel", "oracle"])
def test_compact_slot_scatter_is_exact(which):
    """The compact form carries survivor indices and f32 values through
    one-hot matmuls.  On a TPU the default matmul precision rounds the
    operands to bf16, which corrupts any index above 256 and every f32
    value, while the CPU multiplies exactly; so every such matmul must
    ask for HIGHEST precision, in the kernel and in its oracle."""
    from repro.kernels import topk_compress as tk
    fn = {"kernel": lambda a: tk.topk_compact(a, 4, 128, interpret=True),
          "oracle": lambda a: ref.topk_compact_ref(a, 4, 128)}[which]
    precs = _dot_precisions(
        jax.make_jaxpr(fn)(jnp.zeros((8, 512))).jaxpr, [])
    assert len(precs) == 2, precs
    hi = jax.lax.Precision.HIGHEST
    assert all(p == (hi, hi) for p in precs), precs


@pytest.mark.parametrize("rows,n,k", [(8, 512, 32), (3, 300, 7)])
def test_topk_compress_selects_topk(rows, n, k):
    """Bisection selection must contain >= k entries per row and every
    selected magnitude must be >= every rejected magnitude (threshold
    semantics — the exact top-k up to ties)."""
    acc = jax.random.normal(jax.random.PRNGKey(0), (rows, n))
    sel, mem, cnt = ops.topk_compress(acc, k)
    sel, cnt = np.asarray(sel), np.asarray(cnt)
    a = np.abs(np.asarray(acc))
    for r in range(rows):
        picked = sel[r] != 0
        assert cnt[r] >= k
        assert cnt[r] <= k + 4  # 24 bisection rounds: tight selection
        if picked.any() and (~picked).any():
            assert a[r][picked].min() >= a[r][~picked].max() - 1e-6
    # error identity: selected + memory == input
    np.testing.assert_allclose(sel + np.asarray(mem), np.asarray(acc),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("sign", [False, True])
@pytest.mark.parametrize("rows,n,k,height", [
    (128, 256, 3, 128),      # the rule takes every row in one block
    (32, 65536, 655, 16),    # the plane budget stops it below the rows
    (24, 384, 4, 8),         # no power of two above 8 divides 24
    (9, 256, 3, 8),          # 9 rows: the 8-row grid, padded
    (1, 1024, 10, 1),        # global Top_k's single row
])
def test_topk_compress_rule_height_is_bit_identical(rows, n, k, height,
                                                    sign):
    """The kernel is row-independent: at the height ``block_rows=None``
    picks from the shape, selections, memories and counts equal those
    of the 8-row grid bit for bit."""
    from repro.kernels import topk_compress as tk
    assert tk.dense_block_rows(rows, n) == height
    acc = jax.random.normal(jax.random.PRNGKey(rows + n), (rows, n))
    auto = tk.topk_compress(acc, k, sign=sign, interpret=True)
    eight = tk.topk_compress(acc, k, sign=sign, block_rows=8,
                             interpret=True)
    for a, b in zip(auto, eight):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _cell_launches():
    """(config, rows, n) of every Top_k leaf of the training cells'
    configurations: ``bench/work.leaf_rows`` at the program's dims."""
    import json
    import pathlib
    from bench import model as bmodel
    from bench import work
    configs = pathlib.Path(__file__).resolve().parents[1] / "bench/configs"
    out = set()
    for name in ("yi-6b", "yi-34b"):
        conf = json.loads((configs / f"{name}.json").read_text())
        dm = bmodel.dims(bmodel.program_config(conf))
        out |= {(name, r, n) for r, n in work.leaf_rows(dm)}
    return sorted(out)


@pytest.mark.parametrize("name,rows,n", _cell_launches())
def test_dense_block_rows_at_cell_launches(name, rows, n):
    """Every Top_k launch of the two training cells gets a power-of-two
    height that divides its rows (no launch pads) inside the plane
    budget, and more than 8 rows wherever its leaf has 16 or more."""
    from repro.kernels import topk_compress as tk
    br = tk.dense_block_rows(rows, n)
    assert br & (br - 1) == 0 and rows % br == 0
    assert br * n * 4 <= tk.DENSE_PLANE_BYTES
    assert br > 8 or rows < 16


@pytest.mark.parametrize("rows", [8, 9, 64, 64000])
def test_dense_block_rows_at_max_row(rows):
    """The longest row dispatch admits keeps the 8-row grid that
    compiles for v5e at 2^17 lanes."""
    from repro.kernels import dispatch as dsp
    from repro.kernels import topk_compress as tk
    assert tk.dense_block_rows(rows, dsp.DispatchConfig().max_row) == 8


def test_block_rows_counter_records_each_launch():
    """``launch_stats.BLOCK_ROWS`` counts one entry per ``topk_compress``
    call at trace time, by what set its height: the rule at the height
    it picked, an explicit ``DispatchConfig.block_rows`` as given."""
    from repro.kernels import dispatch as dsp
    from repro.kernels.launch_stats import BLOCK_ROWS, LAUNCHES
    auto = dsp.DispatchConfig(mode="kernel")
    explicit = dsp.DispatchConfig(mode="kernel", block_rows=8)
    x = jnp.zeros((64, 512), jnp.float32)
    dsp.reset_launches()
    jax.make_jaxpr(lambda a: [dsp.topk_rows(a, 5, cfg=auto),
                              dsp.topk_rows(a, 5, sign=True, cfg=auto),
                              dsp.topk_rows(a, 5, cfg=explicit)])(x)
    assert LAUNCHES["topk_compress"] == 3
    assert dict(BLOCK_ROWS) == {("topk_compress", "rule", 64): 2,
                                ("topk_compress", "explicit", 8): 1}
    dsp.reset_launches()
    assert not BLOCK_ROWS


@pytest.mark.parametrize("B,S,H,KV,D,w", [
    (2, 64, 4, 2, 32, -1),
    (1, 100, 4, 4, 16, -1),      # ragged S vs block
    (2, 128, 8, 2, 64, 24),      # sliding window
    (1, 256, 2, 1, 128, 64),     # MQA, bigger head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, KV, D, w, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D)).astype(dtype)
    out = ops.flash_attention(q, k, v, window=w, q_block=32, kv_block=32)
    rout = ref.flash_attention_ref(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), window=w)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(rout, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("rows,n,s", [(6, 512, 15), (1, 128, 3), (13, 257, 255)])
def test_qsgd_matches_ref(rows, n, s):
    x = jax.random.normal(jax.random.PRNGKey(7), (rows, n))
    u = jax.random.uniform(jax.random.PRNGKey(8), (rows, n))
    out = ops.qsgd_quantize(x, u, s)
    rout = ref.qsgd_bucketed_ref(x, u, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(rout),
                               rtol=1e-5, atol=1e-6)


def test_qsgd_unbiased():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 256))
    outs = []
    for i in range(300):
        u = jax.random.uniform(jax.random.PRNGKey(i), x.shape)
        outs.append(np.asarray(ops.qsgd_quantize(x, u, 4)))
    mean = np.mean(outs, axis=0)
    np.testing.assert_allclose(mean, np.asarray(x), atol=0.25)


def test_model_attention_pallas_path_matches_jnp():
    """cfg.use_pallas routes attn_block_train through the kernel."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as tr
    kw = dict(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
              d_ff=128, vocab=97, param_dtype="float32", act_dtype="float32",
              q_chunk=8, max_seq_len=64, scan_layers=False, remat=False)
    cfg_j = ModelConfig(**kw)
    cfg_p = ModelConfig(**{**kw, "use_pallas": True})
    params = tr.init_params(jax.random.PRNGKey(0), cfg_j)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 97)
    lj, _ = tr.loss_fn(params, {"tokens": toks}, cfg_j)
    lp, _ = tr.loss_fn(params, {"tokens": toks}, cfg_p)
    np.testing.assert_allclose(float(lj), float(lp), rtol=1e-4)
