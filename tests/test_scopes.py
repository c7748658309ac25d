"""Named scopes of the round program and stable Pallas kernel names.

Each stage of the mesh engine's round program (``make_dist_steps``)
runs under one ``jax.named_scope`` from ``distributed.ROUND_SCOPES``;
the compiled HLO carries it in every operation's ``op_name``, which a
profiler trace reports as ``tf_op``.  Each ``pallas_call`` carries a
``name=``, its wrapper's ``LAUNCHES`` key.  Compiled on the one CPU
device at a small size; nothing here needs a chip."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.distributed import ROUND_SCOPES, ShardCompressor, make_dist_round
from repro.launch.mesh import make_mesh
from repro.optim import constant, momentum_sgd

D_IN, D_OUT, B = 64, 16, 8
SPECS = {"w": P(None, "model"), "b": P("model")}


def _grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((jnp.tanh(x @ pp["w"]) + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)


def _op_names(wire: str, downlink: bool, partial: bool) -> list:
    """``op_name`` of every operation of the compiled round program
    (H = 2: one local step, then the sync)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    dl = ShardCompressor("topk", 0.1) if downlink else None
    init_fn, round_fn = make_dist_round(
        _grad_fn, momentum_sgd(0.9), ShardCompressor("topk", 0.25),
        constant(0.05), mesh, ("data",), SPECS, wire=wire, downlink=dl,
        partial=partial)
    params = {"w": jnp.zeros((D_IN, D_OUT)), "b": jnp.zeros((D_OUT,))}
    state = jax.eval_shape(init_fn, params)
    block = (jax.ShapeDtypeStruct((2, 1, B, D_IN), jnp.float32),
             jax.ShapeDtypeStruct((2, 1, B, D_OUT), jnp.float32))
    mask = (jax.ShapeDtypeStruct((1,), jnp.bool_),) if partial else ()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = round_fn.lower(state, block, *mask, key).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(op_name: str) -> list:
    return [c for c in op_name.split("/") if c.startswith("qsparse.")]


CASES = {
    "dense": ("dense_psum", False, False),
    "dense-downlink": ("dense_psum", True, False),
    "sparse": ("sparse_allgather", False, False),
    "sparse-downlink": ("sparse_allgather", True, False),
    "dense-partial": ("dense_psum", False, True),
    "sparse-partial-downlink": ("sparse_allgather", True, True),
}


@pytest.fixture(scope="module", params=list(CASES))
def compiled_case(request):
    wire, downlink, partial = CASES[request.param]
    return wire, downlink, _op_names(wire, downlink, partial)


def test_every_stage_scope_reaches_the_compiled_program(compiled_case):
    wire, downlink, names = compiled_case
    want = {"qsparse.grad", "qsparse.optimizer", "qsparse.compress",
            "qsparse.exchange", "qsparse.master_update"}
    if wire == "sparse_allgather":
        want.add("qsparse.decode")
    if downlink:
        want.add("qsparse.downlink")
    found = {s for n in names for s in _scopes(n)}
    assert found == want
    assert found <= set(ROUND_SCOPES)


def test_scopes_do_not_nest(compiled_case):
    _, _, names = compiled_case
    nested = [n for n in names if len(_scopes(n)) > 1]
    assert not nested, nested[:5]


def _pallas_names(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` equation, sub-jaxprs
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += _pallas_names(sub)
    return out


def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_calls():
    from repro.kernels import flash_attention as fa
    from repro.kernels import paged_attention as pa
    from repro.kernels import qsgd
    from repro.kernels import sparse_gemm as sg
    from repro.kernels import topk_compress as tk
    bf = jnp.bfloat16
    return {
        "topk_compress": (lambda a: tk.topk_compress(a, 4),
                          [_sds(8, 256)]),
        "topk_compact": (lambda a: tk.topk_compact(a, 4, 128),
                         [_sds(8, 256)]),
        "qsgd": (lambda x, u: qsgd.qsgd_quantize(x, u, 15),
                 [_sds(8, 256), _sds(8, 256)]),
        "sparse_gemm": (lambda x, i, v: sg.sparse_gemm(x, i, v, 256),
                        [_sds(8, 256, dtype=bf),
                         _sds(128, 128, dtype=jnp.int32), _sds(128, 128)]),
        "qdq_gemm": (lambda x, lv, s: sg.qdq_gemm(x, lv, s),
                     [_sds(8, 256, dtype=bf), _sds(128, 256, dtype=jnp.int8),
                      _sds(128, 1)]),
        "flash_prefill": (lambda q, k, v: fa.flash_attention_fwd(q, k, v),
                          [_sds(1, 128, 4, 128, dtype=bf),
                           _sds(1, 128, 2, 128, dtype=bf),
                           _sds(1, 128, 2, 128, dtype=bf)]),
        "flash_decode": (lambda q, k, v, m: fa.flash_decode_fwd(q, k, v, m),
                         [_sds(1, 1, 4, 128, dtype=bf),
                          _sds(1, 64, 2, 128, dtype=bf),
                          _sds(1, 64, 2, 128, dtype=bf),
                          _sds(64, dtype=jnp.bool_)]),
        "paged_decode": (
            lambda q, kp, vp, ks, vs, t, n: pa.paged_decode_fwd(
                q, kp, vp, ks, vs, t, n),
            [_sds(2, 1, 4, 128, dtype=bf), _sds(8, 32, 2, 128, dtype=bf),
             _sds(8, 32, 2, 128, dtype=bf), _sds(8, 32), _sds(8, 32),
             _sds(2, 4, dtype=jnp.int32), _sds(2, dtype=jnp.int32)]),
    }


@pytest.mark.parametrize("name", [
    "topk_compress", "topk_compact", "qsgd", "sparse_gemm", "qdq_gemm",
    "flash_prefill", "flash_decode", "paged_decode"])
def test_pallas_call_carries_its_kernel_name(name):
    fn, args = _kernel_calls()[name]
    assert _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr) == [name]
