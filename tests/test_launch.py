"""The launchers' shared plumbing: depth cut (``--layers``), in-process
``train.main(argv)``, the Auto-axis mesh helper and the compile-cache
location."""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import compile_cache, mesh as mesh_mod, serve, train


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_layers_refused_with_smoke():
    with pytest.raises(SystemExit):
        train.resolve_config("yi-6b", True, 2)
    with pytest.raises(SystemExit):
        train.main(["--arch", "yi-6b", "--smoke", "--layers", "2"])
    with pytest.raises(SystemExit):
        serve.main(["--arch", "yi-6b", "--smoke", "--layers", "2"])


@pytest.mark.parametrize("layers", [1, 3])
def test_layers_changes_depth_only(layers):
    full = get_config("yi-6b")
    cut = train.resolve_config("yi-6b", False, layers)
    assert cut.n_layers == layers
    assert dataclasses.replace(cut, n_layers=full.n_layers) == full
    assert (cut.d_model, cut.n_heads, cut.n_kv_heads, cut.hd, cut.d_ff,
            cut.vocab, cut.param_dtype) == (4096, 32, 4, 128, 11008, 64000,
                                            "bfloat16")
    assert train.resolve_config("yi-6b", False, None) == full


def test_train_main_in_process(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    res = train.main(["--arch", "yi-6b", "--smoke", "--mesh", "1x1",
                      "--steps", "3", "--H", "2", "--seq", "16",
                      "--batch", "1", "--ckpt", str(tmp_path / "ck")],
                     return_master=True)
    assert res["steps"] == [1, 2, 3]
    assert np.all(np.isfinite(res["losses"]))
    assert res["rounds"] == 2 and res["workers"] == 1
    assert res["bits_up"][1] > 0          # the step-2 sync charged bits
    assert res["state_devices"] == [jax.devices()[0].id]
    assert os.path.isdir(tmp_path / "ck")
    # host copies of the master before and after: same tree, and moved
    m0, m = (jax.tree_util.tree_leaves(res[k]) for k in ("master0", "master"))
    assert [x.shape for x in m0] == [x.shape for x in m]
    assert all(isinstance(x, np.ndarray) for x in m0 + m)
    assert any(not np.array_equal(a, b) for a, b in zip(m0, m))


def test_mesh_helper_axes_are_auto():
    auto = jax.sharding.AxisType.Auto
    m = mesh_mod.make_mesh((1, 1), ("data", "model"))
    assert m.axis_names == ("data", "model")
    assert tuple(m.axis_types) == (auto, auto)
    p = mesh_mod.parse_mesh("1x1x1")
    assert p.axis_names == ("pod", "data", "model")
    assert tuple(p.axis_types) == (auto,) * 3


def test_compile_cache_dir(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.cache_dir() == os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == os.path.join(
        repo, ".jax_cache")
