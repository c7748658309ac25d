"""Production shard_map engine tests (8 forced host devices via
subprocess, so the rest of the suite keeps the real single-device CPU)."""

import pytest

pytestmark = pytest.mark.slow


DIST_MATCHES_REFERENCE = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.core import qsparse, operators as ops, schedule
from repro.optim import sgd, constant

mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out = 4, 16, 8
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params_dev = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

inner = sgd()
comp = ShardCompressor(mode="topk", k_frac=0.25)
init_fn, local_step, sync_step = make_dist_steps(
    grad_fn, inner, comp, constant(0.1), mesh, ("data",), specs)

# reference engine with the equivalent per-leaf operator:
# w [16, 8] model-sharded on axis1 -> _pick_axis keeps axis0 (len 16)
# => per-column top-k over 16 with k_frac 0.25 (k=4 per column);
# b [8] has size <= 8 => the engine skips compression (dense).
class ColTopK(ops.CompressionOp):
    def __call__(self, key, x):
        from repro.core.distributed import axis_topk
        if x.size <= 8:
            return x.astype(jnp.float32), jnp.float32(32 * x.size)
        return axis_topk(x, 0.25, 0)
    def gamma(self, d):
        return 0.25

op_ref = ColTopK()
state_ref = qsparse.init(params, inner, R)
step_ref = jax.jit(qsparse.make_step(grad_fn, inner, op_ref, constant(0.1), R),
                   static_argnames=("sync",))

state = init_fn(params_dev)
ls, ss = jax.jit(local_step), jax.jit(sync_step)
key = jax.random.PRNGKey(1)
H = 4
for t in range(32):
    key, s1, s2 = jax.random.split(key, 3)
    x = jax.random.normal(s1, (R, 16, d_in))
    y = jnp.einsum("rbi,io->rbo", x, Wtrue)
    sync = (t + 1) % H == 0
    if sync:
        state, loss = ss(state, (x, y), s2)
    else:
        state, loss = ls(state, (x, y), s2)
    state_ref, loss_ref = step_ref(state_ref, (x, y), sync=sync, key=s2)
    np.testing.assert_allclose(float(loss), float(loss_ref),
                               rtol=2e-5, atol=2e-5)
np.testing.assert_allclose(np.asarray(state.master["w"]),
                           np.asarray(state_ref.master["w"]),
                           rtol=1e-4, atol=1e-5)
print("DIST==REF OK")
"""


def test_dist_engine_matches_reference(subproc):
    out = subproc(DIST_MATCHES_REFERENCE, devices=8)
    assert "DIST==REF OK" in out


ZERO1_EQUIV = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.optim import sgd, constant

mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out = 4, 16, 8
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

masters = []
for zero1 in (False, True):
    init_fn, local_step, sync_step = make_dist_steps(
        grad_fn, sgd(), ShardCompressor("topk", 0.25), constant(0.1),
        mesh, ("data",), specs, zero1=zero1)
    state = init_fn(params)
    ls, ss = jax.jit(local_step), jax.jit(sync_step)
    key = jax.random.PRNGKey(1)
    for t in range(16):
        key, s1, s2 = jax.random.split(key, 3)
        x = jax.random.normal(s1, (R, 16, d_in))
        y = jnp.einsum("rbi,io->rbo", x, Wtrue)
        if (t + 1) % 4 == 0:
            state, _ = ss(state, (x, y), s2)
        else:
            state, _ = ls(state, (x, y), s2)
    # gather the (possibly zero1-sharded) master
    w = np.asarray(jax.device_get(state.master["w"]))
    masters.append(w)
np.testing.assert_allclose(masters[0], masters[1], rtol=1e-5, atol=1e-6)
print("ZERO1 EQUIV OK")
"""


def test_zero1_equivalent(subproc):
    out = subproc(ZERO1_EQUIV, devices=8)
    assert "ZERO1 EQUIV OK" in out


SPARSE_ALLGATHER = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.optim import sgd, constant

# TP=2 partial-manual mesh: workers manual over 'data', 'model' left to
# XLA's SPMD partitioner.
mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out = 4, 256, 16
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = 0.3 * jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

masters, bits = [], []
for aggregate, disp in (("dense_psum", "reference"),
                        ("sparse_allgather", "kernel")):
    init_fn, local_step, sync_step = make_dist_steps(
        grad_fn, sgd(), ShardCompressor("topk", 0.05, dispatch=disp),
        constant(0.05), mesh, ("data",), specs, aggregate=aggregate)
    state = init_fn(params)
    key = jax.random.PRNGKey(1)
    kb, _ = jax.random.split(key)
    x = jax.random.normal(kb, (R, 8, d_in))
    y = jnp.einsum("rbi,io->rbo", x, Wtrue)
    lowered = jax.jit(sync_step).lower(state, (x, y), key).as_text()
    if aggregate == "sparse_allgather":
        # the kernel compact path is sort-free end to end
        assert "top_k" not in lowered, "lax.top_k leaked into sparse sync"
        assert "sort(" not in lowered, "sort leaked into sparse sync"
    ls, ss = jax.jit(local_step), jax.jit(sync_step)
    for t in range(12):
        key, s1, s2 = jax.random.split(key, 3)
        x = jax.random.normal(s1, (R, 8, d_in))
        y = jnp.einsum("rbi,io->rbo", x, Wtrue)
        if (t + 1) % 4 == 0:
            state, loss = ss(state, (x, y), s2)
        else:
            state, loss = ls(state, (x, y), s2)
    masters.append(np.asarray(jax.device_get(state.master["w"])))
    bits.append(float(state.bits))
# identical math, different wire format: same masters, same counted bits
np.testing.assert_allclose(masters[0], masters[1], rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(bits[0], bits[1])
print("SPARSE==DENSE OK", bits[0])
"""


def test_sparse_allgather_kernel_compact(subproc):
    """aggregate="sparse_allgather" runs through the compact kernel path
    (no lax.top_k in the traced step) and matches the dense-psum
    aggregation state-for-state and bit-for-bit."""
    out = subproc(SPARSE_ALLGATHER, devices=8)
    assert "SPARSE==DENSE OK" in out


DOWNLINK_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.optim import sgd, constant

# TP=2 partial-manual mesh: the downlink runs in BOTH aggregation modes
mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out = 4, 256, 16
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = 0.3 * jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

def run(aggregate, disp, downlink, ddisp):
    dl = None if downlink is None else ShardCompressor(
        "topk", 0.1, dispatch=ddisp)
    init_fn, ls_, ss_ = make_dist_steps(
        grad_fn, sgd(), ShardCompressor("topk", 0.05, dispatch=disp),
        constant(0.05), mesh, ("data",), specs, aggregate=aggregate,
        downlink=dl)
    state = init_fn(params)
    ls, ss = jax.jit(ls_), jax.jit(ss_)
    key = jax.random.PRNGKey(1)
    for t in range(12):
        key, s1, s2 = jax.random.split(key, 3)
        x = jax.random.normal(s1, (R, 8, d_in))
        y = jnp.einsum("rbi,io->rbo", x, Wtrue)
        if (t + 1) % 4 == 0:
            state, loss = ss(state, (x, y), s2)
        else:
            state, loss = ls(state, (x, y), s2)
    return state

# compressed downlink: the dense-psum and sparse-allgather paths must
# agree on worker state and BOTH directions' counted bits.  The sparse
# leg runs the compact kernels for uplink and downlink alike.
sd = run("dense_psum", "reference", "topk", "reference")
sp = run("sparse_allgather", "kernel", "topk", "kernel")
np.testing.assert_allclose(np.asarray(sd.master["w"]),
                           np.asarray(sp.master["w"]),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(np.asarray(sd.view["w"]),
                           np.asarray(sp.view["w"]),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(np.asarray(sd.down_memory["w"]),
                           np.asarray(sp.down_memory["w"]),
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(float(sd.bits), float(sp.bits))
np.testing.assert_allclose(float(sd.bits_down), float(sp.bits_down))
assert float(sd.bits_down) > 0
# post-sync locals equal the views (workers adopt the compressed
# broadcast, not the true master), and views genuinely lag the master
np.testing.assert_allclose(np.asarray(sd.local["w"]),
                           np.asarray(sd.view["w"]), rtol=0, atol=0)
assert float(jnp.max(jnp.abs(sd.view["w"][0] - sd.master["w"]))) > 0

# identity downlink: trajectories and the uplink ledger are
# bit-identical to the downlink-less run; only the new downlink
# ledger differs (dense broadcast cost vs the same dense cost) —
# i.e. exact backward compat plus honest accounting.
s_none = run("dense_psum", "reference", None, None)
from repro.core import bits as bitlib
dense_leaf_bits = sum(32 * v.size for v in params.values())
assert float(s_none.bits_down) == 3 * R * dense_leaf_bits
print("DOWNLINK PARITY OK", float(sd.bits), float(sd.bits_down))
"""


def test_downlink_dense_sparse_parity(subproc):
    """Compressed downlink: dense-psum and sparse-allgather agree on
    worker states and per-direction counted bits on a TP>1
    partial-manual mesh (DESIGN.md §5)."""
    out = subproc(DOWNLINK_PARITY, devices=8)
    assert "DOWNLINK PARITY OK" in out


TP_KERNEL_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.optim import sgd, constant

# TP=2: the Top_k kernels run in a nested region that splits their rows
# over 'model' (kernels/dispatch.py _split_rows); traced, like the
# launcher, without a jax.set_mesh context
mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out = 4, 256, 16
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = 0.3 * jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

def run(wire, disp, downlink):
    dl = ShardCompressor("topk", 0.1, dispatch=disp) if downlink else None
    init_fn, ls_, ss_ = make_dist_steps(
        grad_fn, sgd(), ShardCompressor("topk", 0.05, dispatch=disp),
        constant(0.05), mesh, ("data",), specs, wire=wire, downlink=dl)
    state = init_fn(params)
    ls, ss = jax.jit(ls_), jax.jit(ss_)
    lowered = ss.lower(state, (jnp.zeros((R, 8, d_in)),
                               jnp.zeros((R, 8, d_out))),
                       jax.random.PRNGKey(0)).as_text()
    key = jax.random.PRNGKey(1)
    for t in range(8):
        key, s1, s2 = jax.random.split(key, 3)
        x = jax.random.normal(s1, (R, 8, d_in))
        y = jnp.einsum("rbi,io->rbo", x, Wtrue)
        if (t + 1) % 4 == 0:
            state, loss = ss(state, (x, y), s2)
        else:
            state, loss = ls(state, (x, y), s2)
    return state, lowered

for wire in ("dense_psum", "sparse_allgather"):
    for downlink in (False, True):
        s_kernel, low = run(wire, "kernel", downlink)
        s_ref, _ = run(wire, "reference", downlink)
        # the kernel ran per 'model' shard: a manual region nested in
        # the engine's own
        assert low.count("sdy.manual_computation") >= 2, low
        fields = ["master", "local", "memory"] + (
            ["view", "down_memory"] if downlink else [])
        for f in fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(s_kernel, f)["w"]),
                np.asarray(getattr(s_ref, f)["w"]), err_msg=f)
        assert float(s_kernel.bits) == float(s_ref.bits)
        assert float(s_kernel.bits_down) == float(s_ref.bits_down)
        print("TP KERNEL PARITY OK", wire, downlink, float(s_kernel.bits))
"""


def test_tp_kernel_reference_parity(subproc):
    """On a TP=2 mesh the Top_k kernels (rows split over 'model') match
    the reference dispatch bit for bit on states and both ledgers,
    uplink and downlink, over both wires."""
    out = subproc(TP_KERNEL_PARITY, devices=8)
    assert out.count("TP KERNEL PARITY OK") == 4


HETERO_POLICY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.core import policy as pol
from repro.optim import sgd, constant

# heterogeneous per-leaf policy (DESIGN.md §6) on a TP=2
# partial-manual mesh: Top_k on the matmul, QSGD on the embedding,
# dense on the bias — through BOTH aggregation paths, which must agree
# on states and counted bits (acceptance criterion).
mesh = make_mesh((4, 2), ("data", "model"))
R, d_in, d_out, V = 4, 256, 16, 64
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,)),
          "embed": jnp.zeros((V, d_in))}
specs = {"w": P(None, "model"), "b": P("model"), "embed": P(None, None)}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = 0.3 * jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    def f(pp):
        h = jnp.take(pp["embed"], jnp.arange(8) % V, axis=0)
        return (jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
                + 1e-3 * jnp.sum(h ** 2))
    return jax.value_and_grad(f)(p)

POLICY = "b->identity; embed->qsgd:s=15; .*->topk:k=0.05"

def run(aggregate, disp):
    comp = ShardCompressor.from_spec(POLICY, params, dispatch=disp)
    assert comp.mode == "policy"
    init_fn, ls_, ss_ = make_dist_steps(
        grad_fn, sgd(), comp, constant(0.05), mesh, ("data",), specs,
        aggregate=aggregate)
    state = init_fn(params)
    ls, ss = jax.jit(ls_), jax.jit(ss_)
    key = jax.random.PRNGKey(1)
    for t in range(12):
        key, s1, s2 = jax.random.split(key, 3)
        x = jax.random.normal(s1, (R, 8, d_in))
        y = jnp.einsum("rbi,io->rbo", x, Wtrue)
        if (t + 1) % 4 == 0:
            state, loss = ss(state, (x, y), s2)
        else:
            state, loss = ls(state, (x, y), s2)
    return state

# the dense leg runs the reference path, the sparse leg the compact
# kernels for the Top_k leaf
sd = run("dense_psum", "reference")
sp = run("sparse_allgather", "kernel")
for k in ("w", "b", "embed"):
    np.testing.assert_allclose(np.asarray(sd.master[k]),
                               np.asarray(sp.master[k]),
                               rtol=1e-4, atol=1e-5)
# identical math (the QSGD draw shares the key stream across paths):
# counted bits agree exactly, and the stochastic leaf transmitted
np.testing.assert_allclose(float(sd.bits), float(sp.bits))
assert float(sd.bits) > 0
print("HETERO POLICY PARITY OK", float(sd.bits))
"""


def test_hetero_policy_dense_sparse_parity(subproc):
    """A heterogeneous per-leaf policy (TopK + QSGD + identity) trains
    through both distributed aggregation paths on a TP>1 mesh, with
    dense-psum and sparse-allgather agreeing on states
    and counted bits."""
    out = subproc(HETERO_POLICY, devices=8)
    assert "HETERO POLICY PARITY OK" in out


MULTIPOD = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, ShardCompressor
from repro.optim import sgd, constant

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
R = 4  # pod * data
d_in, d_out = 16, 8
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

init_fn, local_step, sync_step = make_dist_steps(
    grad_fn, sgd(), ShardCompressor("topk", 0.5), constant(0.1),
    mesh, ("pod", "data"), specs)
state = init_fn(params)
ls, ss = jax.jit(local_step), jax.jit(sync_step)
key = jax.random.PRNGKey(1)
for t in range(160):
    key, s1, s2 = jax.random.split(key, 3)
    x = jax.random.normal(s1, (R, 16, d_in))
    y = jnp.einsum("rbi,io->rbo", x, Wtrue)
    if (t + 1) % 4 == 0:
        state, loss = ss(state, (x, y), s2)
    else:
        state, loss = ls(state, (x, y), s2)
assert float(loss) < 0.1, float(loss)
print("MULTIPOD OK", float(loss))
"""


def test_multipod_axes(subproc):
    out = subproc(MULTIPOD, devices=8)
    assert "MULTIPOD OK" in out


ROUND_PROGRAM_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import (make_dist_round, make_dist_steps,
                                    ShardCompressor)
from repro.optim import sgd, constant

mesh = make_mesh((8, 1), ("data", "model"))
R, d_in, d_out = 8, 16, 8
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(None, "model"), "b": P("model")}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wtrue = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

key0 = jax.random.PRNGKey(7)
bs = []
for _ in range(16):
    key0, s = jax.random.split(key0)
    x = jax.random.normal(s, (R, 16, d_in))
    bs.append((x, jnp.einsum("rbi,io->rbo", x, Wtrue)))

H, T = 4, 16
for agg in ("dense_psum", "sparse_allgather"):
    for dl in (None, ShardCompressor("topk", 0.5)):
        comp = ShardCompressor("topk", 0.25)
        common = dict(aggregate=agg, downlink=dl)
        init_fn, ls, ss = make_dist_steps(
            grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
            **common)
        st = init_fn(params)
        lsj, ssj = jax.jit(ls), jax.jit(ss)
        key = jax.random.PRNGKey(1)
        ref_losses = []
        for t in range(T):
            key, sub = jax.random.split(key)
            step = ssj if (t + 1) % H == 0 else lsj
            st, loss = step(st, bs[t], sub)
            ref_losses.append(float(loss))
        ref = st
        init_fn2, round_fn = make_dist_round(
            grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
            **common)
        st2 = init_fn2(params)
        key = jax.random.PRNGKey(1)
        losses2 = []
        for r0 in range(0, T, H):
            block = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *bs[r0:r0 + H])
            st2, larr, key = round_fn(st2, block, key)
            losses2.extend(np.asarray(larr).tolist())
        # bit-for-bit: states and both direction ledgers
        np.testing.assert_array_equal(np.asarray(ref.master["w"]),
                                      np.asarray(st2.master["w"]))
        np.testing.assert_array_equal(np.asarray(ref.local["w"]),
                                      np.asarray(st2.local["w"]))
        np.testing.assert_array_equal(np.asarray(ref.memory["w"]),
                                      np.asarray(st2.memory["w"]))
        assert float(ref.bits) == float(st2.bits)
        assert float(ref.bits_down) == float(st2.bits_down)
        assert int(ref.rounds) == int(st2.rounds)
        np.testing.assert_array_equal(np.asarray(ref_losses),
                                      np.asarray(losses2))
        print("ROUND FUSED OK", agg, "downlink" if dl else "nodl")

# TP>1 mesh: the same fused round program partitions over 'model' too
mesh2 = make_mesh((4, 2), ("data", "model"))
params2 = jax.device_put(
    {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))},
    jax.tree.map(lambda s: NamedSharding(mesh2, s), specs,
                 is_leaf=lambda z: isinstance(z, P)))
init_fn3, round_fn3 = make_dist_round(
    grad_fn, sgd(), ShardCompressor("topk", 0.25), constant(0.1),
    mesh2, ("data",), specs)
bs2 = [(b[0][:4], b[1][:4]) for b in bs[:4]]
st3 = init_fn3(params2)
block = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *bs2)
st3, larr, _ = round_fn3(st3, block, jax.random.PRNGKey(1))
assert np.all(np.isfinite(np.asarray(larr)))
assert int(st3.rounds) == 1
print("ROUND TP2 OK")
"""


def test_round_program_parity(subproc):
    """DESIGN.md §7: the fused mesh round program (lax.scan over the
    shard_mapped local step + sync at the tail, donated state) is
    bit-for-bit the per-step path on states and both direction ledgers
    for both aggregations, with and without a compressed downlink —
    and runs on a TP>1 mesh too."""
    out = subproc(ROUND_PROGRAM_PARITY, devices=8, timeout=1500)
    assert out.count("ROUND FUSED OK") == 4
    assert "ROUND TP2 OK" in out
