"""Tests for the overlapped round driver (DESIGN.md §10).

The feature's whole contract is *scheduling only*: windows of
consecutive equal-length rounds execute as one scanned multi-round
program (``rounds.window_rounds`` → ``engine.make_multiround`` /
``distributed.make_dist_multiround``), and every trajectory — state
leaves, both wire-bit ledgers, per-step losses, trainer History — is
bit-for-bit the serialized round runtime's.  These tests pin that
across sync/async/scenario masks, compressed downlinks, the per-leaf
ledger, truncated batch streams, eval/ckpt boundaries, and the mesh
engine.
"""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import operators as ops
from repro.core import rounds as rnd
from repro.core import schedule as sched
from repro.optim import transforms as tfm
from tests.strategies import mask_grid

# ---------------------------------------------------------------------------
# window_rounds (pure host)
# ---------------------------------------------------------------------------


def _plans_of(mask):
    return rnd.compile_rounds(mask)


@pytest.mark.parametrize("name,mask", mask_grid())
def test_window_rounds_partition(name, mask):
    plans = _plans_of(mask)
    for w in (1, 2, 4, 8):
        windows = rnd.window_rounds(plans, max_window=w)
        flat = [p for win in windows for p in win]
        assert flat == plans, name
        for win in windows:
            assert len(win) <= w
            assert len(win) & (len(win) - 1) == 0, "power-of-two sizes"
            assert len({p.length for p in win}) == 1, \
                "windows are rectangular"


def test_window_rounds_boundary_singletons():
    mask = sched.fixed_schedule(32, 4)
    plans = _plans_of(mask)
    # steps 11 and 23 (0-based) are eval points: their rounds must be
    # singleton windows so the driver can materialize the state there
    windows = rnd.window_rounds(plans, max_window=8,
                                boundary_steps=(11, 23))
    for win in windows:
        for p in win:
            if any(p.start <= b < p.stop for b in (11, 23)):
                assert len(win) == 1
    assert [p for w in windows for p in w] == plans


def test_window_rounds_rejects_bad_window():
    with pytest.raises(ValueError):
        rnd.window_rounds([], max_window=0)


# ---------------------------------------------------------------------------
# engine: run_rounds_overlap ≡ run_rounds
# ---------------------------------------------------------------------------

R, D, T = 4, 96, 24


def _problem():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(64, D)).astype(np.float32))
    y = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    params = {"w": jnp.zeros((D,), jnp.float32),
              "b": jnp.zeros((3, 8), jnp.float32)}

    def loss(p, batch):
        xb, yb = batch
        pred = xb @ p["w"] + p["b"].sum()
        return jnp.mean((pred - yb) ** 2)

    def batches(n=T):
        r = np.random.default_rng(5)
        for _ in range(n):
            idx = r.integers(0, 64, size=(R, 16))
            yield (A[jnp.asarray(idx)], y[jnp.asarray(idx)])

    return jax.value_and_grad(loss), params, batches


def _assert_same(state_a, state_b, losses_a, losses_b, ctx=""):
    la, lb = np.asarray(losses_a), np.asarray(losses_b)
    assert la.shape == lb.shape and np.array_equal(la, lb), (ctx, "losses")
    fa = jax.tree_util.tree_leaves(state_a)
    fb = jax.tree_util.tree_leaves(state_b)
    assert len(fa) == len(fb)
    for xa, xb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=str(ctx))


@pytest.mark.parametrize("name,mask", mask_grid(T=T, R=R, H=3))
def test_overlap_matches_serial(name, mask):
    grad_fn, params, batches = _problem()
    inner = tfm.sgd(0.05)
    sup = eng.make_superstep(grad_fn, inner, ops.TopK(0.25),
                             lambda t: 0.05, R)
    s_a, l_a = eng.run_rounds(eng.init(params, inner, R), sup, batches(),
                              mask, jax.random.PRNGKey(7))
    s_b, l_b = eng.run_rounds_overlap(
        eng.init(params, inner, R), sup, batches(), mask,
        jax.random.PRNGKey(7), window=4)
    _assert_same(s_a, s_b, l_a, l_b, name)


@pytest.mark.parametrize("downlink,leaf", [
    (None, True),
    (ops.QSGDQuantizer(4), False),
    (ops.QSGDQuantizer(4), True),
])
def test_overlap_matches_serial_channels(downlink, leaf):
    """Both bits ledgers (and the per-leaf split) survive windowing,
    with and without a compressed downlink."""
    grad_fn, params, batches = _problem()
    inner = tfm.sgd(0.05)
    mask = sched.async_schedule(T, R, 3, seed=11)
    sup = eng.make_superstep(grad_fn, inner, ops.TopK(0.25),
                             lambda t: 0.05, R, downlink=downlink,
                             leaf_ledger=leaf)
    s_a, l_a = eng.run_rounds(
        eng.init(params, inner, R, downlink=downlink, leaf_ledger=leaf),
        sup, batches(), mask, jax.random.PRNGKey(7))
    s_b, l_b = eng.run_rounds_overlap(
        eng.init(params, inner, R, downlink=downlink, leaf_ledger=leaf),
        sup, batches(), mask, jax.random.PRNGKey(7), window=8)
    _assert_same(s_a, s_b, l_a, l_b, (downlink, leaf))
    assert float(s_a.bits) == float(s_b.bits) > 0
    assert float(np.asarray(s_a.bits_down)) == float(
        np.asarray(s_b.bits_down))


def test_overlap_truncated_stream():
    """A batch stream that dries up mid-window serializes the leftover
    rounds exactly like run_rounds (zeros tail on the partial round)."""
    grad_fn, params, batches = _problem()
    inner = tfm.sgd(0.05)
    fixed = sched.fixed_schedule(T, 3)
    mask = np.broadcast_to(fixed[:, None], (T, R)).copy()
    for cut in (T - 5, 7, 2):
        sup = eng.make_superstep(grad_fn, inner, ops.TopK(0.25),
                                 lambda t: 0.05, R)
        s_a, l_a = eng.run_rounds(eng.init(params, inner, R), sup,
                                  batches(cut), mask, jax.random.PRNGKey(7))
        s_b, l_b = eng.run_rounds_overlap(
            eng.init(params, inner, R), sup, batches(cut), mask,
            jax.random.PRNGKey(7), window=4)
        _assert_same(s_a, s_b, l_a, l_b, f"cut={cut}")


def test_multiround_emits_per_round_ledgers():
    """The scanned window reports each interior round boundary's ledger
    — what keeps the trainer's History exact without materializing
    mid-window states."""
    grad_fn, params, batches = _problem()
    inner = tfm.sgd(0.05)
    fixed = sched.fixed_schedule(T, 3)
    mask = np.broadcast_to(fixed[:, None], (T, R)).copy()
    plans = rnd.compile_rounds(mask)[:4]
    sup = eng.make_superstep(grad_fn, inner, ops.TopK(0.25),
                             lambda t: 0.05, R)
    # serial reference ledgers at each round boundary
    state = eng.init(params, inner, R)
    key = jax.random.PRNGKey(7)
    it = iter(batches())
    ref = []
    for p in plans:
        block = eng.stack_block([next(it) for _ in range(p.length)])
        state, _, key = sup(state, block, jnp.asarray(p.mask), key)
        ref.append((float(state.bits), int(state.rounds)))
    multi = eng.make_multiround(sup)
    state2 = eng.init(params, inner, R)
    it = iter(batches())
    steps = [next(it) for _ in range(sum(p.length for p in plans))]
    blocks = eng.stack_window(steps, len(plans), plans[0].length)
    masks = jnp.asarray(np.stack([np.asarray(p.mask) for p in plans]))
    _, _, leds, _ = multi(state2, blocks, masks, jax.random.PRNGKey(7))
    got = [(float(b), int(r)) for b, r in
           zip(np.asarray(leds["bits"]), np.asarray(leds["rounds"]))]
    assert got == ref


# ---------------------------------------------------------------------------
# trainer: History parity + guards
# ---------------------------------------------------------------------------


def _train_pair(run_kw, policy="topk:k=0.25"):
    from repro.train import trainer as tr
    grad_fn, params, batches = _problem()

    def eval_fn(master):
        return {"norm": float(jnp.sum(master["w"] ** 2))}

    out = {}
    for overlap in (False, True):
        with tempfile.TemporaryDirectory() as td:
            run = tr.RunConfig(total_steps=T, R=R, seed=3, log_every=5,
                               eval_every=10, ckpt_dir=td, ckpt_every=12,
                               policy=policy, overlap=overlap,
                               overlap_window=4, **run_kw)
            st, h = tr.train(grad_fn, params, tfm.sgd(0.05),
                             lr_schedule=lambda t: 0.05,
                             batches=batches(), run=run, eval_fn=eval_fn)
        d = dataclasses.asdict(h)
        d.pop("wall_time")
        out[overlap] = (np.asarray(st.master["w"]), d, float(st.bits))
    return out


@pytest.mark.parametrize("run_kw,policy", [
    (dict(H=3), "topk:k=0.25"),
    (dict(H=3, asynchronous=True), "topk:k=0.25"),
    (dict(H=2, leaf_ledger=True), "topk:k=0.25 >> qsgd:s=4"),
    (dict(H=4, scenario="participation=0.7,seed=2"), "topk:k=0.25"),
])
def test_trainer_overlap_history_identical(run_kw, policy):
    pair = _train_pair(run_kw, policy)
    wa, ha, ba = pair[False]
    wb, hb, bb = pair[True]
    np.testing.assert_array_equal(wa, wb)
    assert ba == bb
    assert ha == hb, {k: (ha[k], hb[k]) for k in ha if ha[k] != hb[k]}


def test_trainer_overlap_guards():
    from repro.train import trainer as tr
    grad_fn, params, batches = _problem()
    for bad in (dict(runtime="step"), dict(faults="preset:none")):
        run = tr.RunConfig(total_steps=4, R=R, policy="topk:k=0.25",
                           overlap=True, **bad)
        with pytest.raises(ValueError):
            tr.train(grad_fn, params, tfm.sgd(0.05),
                     lr_schedule=lambda t: 0.05, batches=batches(4),
                     run=run)


# ---------------------------------------------------------------------------
# mesh engine: make_dist_multiround ≡ make_dist_round
# ---------------------------------------------------------------------------

DIST_MULTIROUND = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import (make_dist_round, make_dist_multiround,
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
comp = ShardCompressor("topk", 0.25)
for dl in (None, ShardCompressor("topk", 0.5)):
    init_fn, round_fn = make_dist_round(
        grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
        downlink=dl)
    init2, multi_fn = make_dist_multiround(
        grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
        downlink=dl)
    st = init_fn(params)
    key = jax.random.PRNGKey(1)
    ref_losses = []
    for r0 in range(0, T, H):
        block = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *bs[r0:r0 + H])
        st, larr, key = round_fn(st, block, key)
        ref_losses.append(np.asarray(larr))
    st2 = init2(params)
    blocks = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs).reshape((T // H, H) + xs[0].shape),
        *bs)
    st2, larr2, _ = multi_fn(st2, blocks, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.stack(ref_losses), np.asarray(larr2))
    np.testing.assert_array_equal(np.asarray(st.master["w"]),
                                  np.asarray(st2.master["w"]))
    np.testing.assert_array_equal(np.asarray(st.memory["w"]),
                                  np.asarray(st2.memory["w"]))
    assert float(st.bits) == float(st2.bits)
    assert float(st.bits_down) == float(st2.bits_down)
    assert int(st.rounds) == int(st2.rounds)
    print("DIST MULTIROUND OK", "downlink" if dl else "nodl")

# partial=True: per-round tail masks stack to [W, R]
init_fn, round_fn = make_dist_round(
    grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
    partial=True)
init2, multi_fn = make_dist_multiround(
    grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
    partial=True)
rngm = np.random.default_rng(3)
masks = jnp.asarray(rngm.random((T // H, R)) < 0.6)
st = init_fn(params)
key = jax.random.PRNGKey(1)
ref_losses = []
for w, r0 in enumerate(range(0, T, H)):
    block = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *bs[r0:r0 + H])
    st, larr, key = round_fn(st, block, masks[w], key)
    ref_losses.append(np.asarray(larr))
st2 = init2(params)
blocks = jax.tree_util.tree_map(
    lambda *xs: jnp.stack(xs).reshape((T // H, H) + xs[0].shape), *bs)
st2, larr2, _ = multi_fn(st2, blocks, masks, jax.random.PRNGKey(1))
np.testing.assert_array_equal(np.stack(ref_losses), np.asarray(larr2))
np.testing.assert_array_equal(np.asarray(st.master["w"]),
                              np.asarray(st2.master["w"]))
assert float(st.bits) == float(st2.bits)
print("DIST MULTIROUND PARTIAL OK")
"""


def test_dist_multiround_parity(subproc):
    out = subproc(DIST_MULTIROUND, devices=8, timeout=1500)
    assert out.count("DIST MULTIROUND OK") == 2
    assert "DIST MULTIROUND PARTIAL OK" in out
