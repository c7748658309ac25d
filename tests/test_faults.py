"""Staleness-first fault runtime tests (DESIGN.md §9).

The acceptance pins for the fault runtime, in one suite:

* fault tables are deterministic in the dedicated fault seed and obey
  the spec invariants (delay windows, crash/recover structure);
* with trivial tables the fault runtime is **bit-for-bit** the
  fault-free runtime — enabling the queue machinery (or flipping the
  fault seed) never perturbs the jax data/model key stream (S1);
* a payload computed at t is executed at t+τ — differential test of
  the engine against a plain-numpy oracle that replays the documented
  semantics step by step;
* the compiled fault-round programs match the per-step loop exactly,
  fault-free and under chaos;
* crash → recover re-initializes from the master and zeroes the error
  memory; dead workers are frozen;
* an all-crashed round is a no-op sync: master untouched, zero bits,
  an empty History round (S2);
* the trainer surface: ``faults="preset:none"`` bit-exact, step/round
  runtime parity, crash-consistent resume restoring the in-flight
  queue exactly;
* both distributed transports execute the same faults (slow/subprocess
  twins live at the bottom).
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import engine, operators as ops, rounds as rnd, \
    scenarios as scn, schedule as sched
from repro.optim import constant, sgd
from tests.strategies import FAULT_GRID, fault_schedules, fault_specs

R, D, T, H = 4, 24, 20, 4


# ---------------------------------------------------------------------------
# fault tables: determinism + invariants
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(spec=fault_specs())
def test_tables_deterministic_and_invariant(spec):
    t1 = spec.tables(T, R)
    t2 = spec.tables(T, R)
    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(a, b)
    assert t1.delay.shape == (T, R) and t1.delay.dtype == np.int32
    assert (t1.delay >= spec.min_delay).all()
    assert (t1.delay <= spec.max_delay).all()
    assert t1.depth <= spec.depth
    # recover fires exactly on the first alive step after an outage
    assert not t1.recover[0].any()
    np.testing.assert_array_equal(
        t1.recover[1:], t1.alive[1:] & ~t1.alive[:-1])


@pytest.mark.parametrize("spec", FAULT_GRID)
def test_grid_tables_cover_crash_windows(spec):
    tables = spec.tables(T, R)
    for w, c, rec in spec.crash:
        if w < R:
            assert not tables.alive[min(c, T):min(rec, T), w].any()
    if spec == scn.FaultSpec():
        assert tables.trivial


def test_trivial_tables_ignore_seed():
    """The fault seed feeds only the fault PRNG: a no-fault spec yields
    identical (trivial) tables whatever the seed (S1)."""
    for seed in (0, 1, 123):
        t = scn.FaultSpec(seed=seed).tables(T, R)
        assert t.trivial
        np.testing.assert_array_equal(t.delay,
                                      np.zeros((T, R), np.int32))


def test_parse_roundtrip_and_presets():
    for spec in FAULT_GRID:
        assert scn.parse_faults(spec.to_string()) == spec
    for name in scn.FAULT_PRESETS:
        assert scn.parse_faults(f"preset:{name}") == scn.FAULT_PRESETS[name]
    with pytest.raises(KeyError):
        scn.parse_faults("preset:nope")
    with pytest.raises(KeyError):
        scn.parse_faults("bogus_knob=1")
    with pytest.raises(ValueError):
        scn.FaultSpec(min_delay=3, max_delay=1)
    with pytest.raises(ValueError):
        scn.FaultSpec(crash=((0, 5, 2),))


# ---------------------------------------------------------------------------
# host-side replay + round segmentation under faults
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(case=fault_schedules())
def test_fault_replay_conserves_payloads(case):
    mask, tables = case
    Tt, Rr = mask.shape
    computed, arrivals, events = scn.fault_replay(mask, tables)
    np.testing.assert_array_equal(computed, mask & tables.alive)
    # every computed, undropped payload either lands within the window
    # or is still in flight past T-1 — none is duplicated or invented
    src = computed & ~tables.drop
    landed = sum(1 for t, r in zip(*np.nonzero(src))
                 if t + int(tables.delay[t, r]) < Tt)
    assert int(arrivals.sum()) == landed
    np.testing.assert_array_equal(
        events, mask.any(axis=1) | (arrivals > 0).any(axis=1))


@settings(max_examples=25, deadline=None)
@given(case=fault_schedules())
def test_fault_rounds_close_at_events(case):
    mask, tables = case
    _, _, events = scn.fault_replay(mask, tables)
    plans = rnd.compile_fault_rounds(mask, tables)
    pos = 0
    for p in plans:
        assert p.start == pos
        # heads are event-free; tails are events (or the trailing
        # partial round, which has no event at all)
        assert not events[p.start:p.stop - 1].any()
        pos = p.stop
    assert pos == mask.shape[0]
    np.testing.assert_array_equal(rnd.expand_rounds(plans), mask)
    if tables.trivial:
        base = rnd.compile_rounds(mask)
        assert [(p.start, p.length) for p in plans] == \
            [(p.start, p.length) for p in base]


def test_fault_rounds_extra_events_split():
    mask = sched.fixed_schedule(12, 4)
    tables = scn.FaultSpec().tables(12, 1)
    plans = rnd.compile_fault_rounds(mask, tables, extra_events=[1])
    assert plans[0].length == 2 and not plans[0].syncs
    np.testing.assert_array_equal(rnd.expand_rounds(plans), mask)


# ---------------------------------------------------------------------------
# engine: problem fixture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def problem():
    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(64, D).astype(np.float32))
    y = jnp.asarray(rng.randn(64).astype(np.float32))

    def grad_fn(params, batch):
        Ab, yb = A[batch], y[batch]

        def loss_fn(w):
            r = Ab @ w - yb
            return 0.5 * jnp.mean(r * r)

        l, g = jax.value_and_grad(loss_fn)(params["w"])
        return l, {"w": g}

    params = {"w": jnp.zeros((D,), jnp.float32)}
    batches = [jnp.asarray(rng.randint(0, 64, size=(R, 8)))
               for _ in range(T)]
    mask = sched.async_schedule(T, R, H, seed=3)
    return grad_fn, params, batches, mask


def _run_faulty(problem, spec, op, *, rounds=False, **kw):
    grad_fn, params, batches, mask = problem
    tables = spec.tables(T, R)
    state = engine.init(params, sgd(), R, queue_depth=spec.depth)
    key = jax.random.PRNGKey(42)
    if rounds:
        sup = engine.make_fault_superstep(
            grad_fn, sgd(), op, constant(0.05), R,
            queue_depth=spec.depth, **kw)
        return engine.run_fault_rounds(state, sup, batches, mask, tables,
                                       key)
    step = engine.make_fault_step(
        grad_fn, sgd(), op, constant(0.05), R,
        queue_depth=spec.depth, **kw)
    return engine.run_faults(state, step, batches, mask, tables, key)


# ---------------------------------------------------------------------------
# S1: trivial tables are bit-for-bit the fault-free runtime
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", [
    ops.TopK(k=6),
    # randomized quantizer: pins that the fault machinery consumes the
    # exact same key-split sequence as the baseline step
    ops.QuantizedSparsifier(k=6, s=15),
], ids=["topk", "qtopk"])
def test_trivial_faults_bit_exact(problem, op):
    grad_fn, params, batches, mask = problem
    key = jax.random.PRNGKey(42)
    base_state = engine.init(params, sgd(), R)
    base_step = engine.make_step(grad_fn, sgd(), op, constant(0.05), R)
    base, base_losses = engine.run(base_state, base_step, batches, mask,
                                   key)
    # any fault seed: trivial tables are seed-independent
    faulty, fl = _run_faulty(problem, scn.FaultSpec(seed=7), op)
    for field in ("master", "local", "memory"):
        np.testing.assert_array_equal(
            np.asarray(getattr(base, field)["w"]),
            np.asarray(getattr(faulty, field)["w"]))
    np.testing.assert_array_equal(np.asarray(base.bits),
                                  np.asarray(faulty.bits))
    np.testing.assert_array_equal(np.asarray(base.rounds),
                                  np.asarray(faulty.rounds))
    np.testing.assert_array_equal(np.asarray(base_losses), np.asarray(fl))
    # τ ≡ 0: enqueue and apply collapse — the queue never holds state
    assert not np.asarray(faulty.inflight["w"]).any()
    assert (np.asarray(faulty.arrive_at) == -1).all()


# ---------------------------------------------------------------------------
# tentpole: executed delayed payloads vs a plain-numpy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", FAULT_GRID,
                         ids=[f"spec{i}" for i in range(len(FAULT_GRID))])
def test_engine_matches_numpy_oracle(problem, spec):
    """Identity compression makes the error-feedback algebra exact
    (memory stays zero), so a hand-rolled numpy replay of the §9
    semantics — compute at t, enqueue, execute at t+τ, broadcast on
    arrival — must reproduce the engine's master trajectory."""
    grad_fn, params, batches, mask = problem
    tables = spec.tables(T, R)
    lr = np.float32(0.05)

    # ---- oracle ---------------------------------------------------
    Dq = spec.depth
    master = np.zeros(D, np.float32)
    local = np.zeros((R, D), np.float32)
    view = np.zeros((R, D), np.float32)
    q = np.zeros((R, Dq, D), np.float32)
    arrive = np.full((R, Dq), -1, np.int64)
    for t in range(T):
        for r in range(R):
            if tables.recover[t, r]:
                local[r] = master
                view[r] = master
        alive = tables.alive[t]
        half = local.copy()
        for r in range(R):
            if alive[r]:
                _, g = grad_fn({"w": jnp.asarray(local[r])},
                               np.asarray(batches[t][r]))
                half[r] = local[r] - lr * np.asarray(g["w"], np.float32)
        compute = mask[t] & alive
        if not (compute.any() or (arrive == t).any()):
            local = half
            continue
        slot = t % Dq
        for r in range(R):
            if compute[r] and not tables.drop[t, r]:
                q[r, slot] = view[r] - half[r]    # memory ≡ 0 (Identity)
                arrive[r, slot] = t + int(tables.delay[t, r])
        arr = arrive == t
        master = master - (q * arr[..., None]).sum(axis=(0, 1)) / R
        q[arr] = 0.0
        arrive[arr] = -1
        received = arr.any(axis=1) & alive
        local = half
        for r in range(R):
            if received[r]:
                view[r] = master
                local[r] = master

    # ---- engine ---------------------------------------------------
    state, _ = _run_faulty(problem, spec, ops.Identity())
    np.testing.assert_allclose(np.asarray(state.master["w"]), master,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state.local["w"]), local,
                               rtol=1e-5, atol=1e-6)
    # Identity keeps the uplink error memory exactly zero throughout
    assert not np.asarray(state.memory["w"]).any()
    np.testing.assert_array_equal(np.asarray(state.arrive_at), arrive)


# ---------------------------------------------------------------------------
# round program parity under faults
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", FAULT_GRID,
                         ids=[f"spec{i}" for i in range(len(FAULT_GRID))])
def test_fault_round_matches_per_step(problem, spec):
    op = ops.TopK(k=6)
    s1, l1 = _run_faulty(problem, spec, op)
    s2, l2 = _run_faulty(problem, spec, op, rounds=True)
    for field in ("master", "local", "memory", "inflight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s1, field)["w"]),
            np.asarray(getattr(s2, field)["w"]))
    np.testing.assert_array_equal(np.asarray(s1.arrive_at),
                                  np.asarray(s2.arrive_at))
    np.testing.assert_array_equal(np.asarray(s1.bits),
                                  np.asarray(s2.bits))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_staleness_weight_damped_differs(problem):
    spec = scn.FaultSpec(max_delay=3, seed=1)
    su, _ = _run_faulty(problem, spec, ops.TopK(k=6))
    sd, _ = _run_faulty(problem, spec, ops.TopK(k=6),
                        staleness_weight="damped")
    assert np.isfinite(np.asarray(sd.master["w"])).all()
    # delayed payloads are scaled by 1/(1+τ): trajectories must differ
    assert not np.array_equal(np.asarray(su.master["w"]),
                              np.asarray(sd.master["w"]))


# ---------------------------------------------------------------------------
# crash → recover semantics
# ---------------------------------------------------------------------------


def test_crash_freezes_and_recover_reinitializes(problem):
    grad_fn, params, batches, mask_ = problem
    crash_t, rec_t, w = 5, 11, 1
    spec = scn.FaultSpec(crash=((w, crash_t, rec_t),))
    tables = spec.tables(T, R)
    mask = np.asarray(mask_, bool).copy()
    mask[rec_t, :] = False            # recover step takes no sync
    rows = engine.fault_rows(mask, tables, R)
    state = engine.init(params, sgd(), R, queue_depth=spec.depth)
    step = engine._donated(engine.make_fault_step(
        grad_fn, sgd(), ops.TopK(k=6), constant(0.05), R,
        queue_depth=spec.depth))
    key = jax.random.PRNGKey(42)
    snap = None
    for t in range(rec_t + 1):
        if t == crash_t:
            snap = jax.tree.map(np.asarray,
                                {"local": state.local["w"][w],
                                 "memory": state.memory["w"][w],
                                 "view": state.master_view["w"][w]})
        key, sub = jax.random.split(key)
        state, _ = step(state, batches[t], engine.index_rows(rows, t), sub)
        if crash_t <= t < rec_t:
            # dead: iterate, memory and view frozen at pre-crash values
            np.testing.assert_array_equal(
                np.asarray(state.local["w"][w]), snap["local"])
            np.testing.assert_array_equal(
                np.asarray(state.memory["w"][w]), snap["memory"])
            np.testing.assert_array_equal(
                np.asarray(state.master_view["w"][w]), snap["view"])
    # the recover step ran: memory lost, view = master, local = master
    # plus exactly one local sgd step taken from the master
    assert not np.asarray(state.memory["w"][w]).any()
    master_before = np.asarray(state.master["w"])   # untouched at rec_t
    np.testing.assert_array_equal(
        np.asarray(state.master_view["w"][w]), master_before)
    _, g = grad_fn({"w": jnp.asarray(master_before)},
                   np.asarray(batches[rec_t][w]))
    np.testing.assert_allclose(
        np.asarray(state.local["w"][w]),
        master_before - 0.05 * np.asarray(g["w"]), rtol=1e-6, atol=1e-7)


def test_all_crashed_round_is_noop(problem):
    """S2: a fleet that is entirely dead across the whole schedule
    produces no payloads — the master never moves, both bits ledgers
    stay zero, and no round is counted."""
    spec = scn.FaultSpec(crash=tuple((r, 0, T + 1) for r in range(R)))
    for rounds in (False, True):
        state, losses = _run_faulty(problem, spec, ops.TopK(k=6),
                                    rounds=rounds)
        np.testing.assert_array_equal(np.asarray(state.master["w"]),
                                      np.zeros(D, np.float32))
        np.testing.assert_array_equal(np.asarray(state.local["w"]),
                                      np.zeros((R, D), np.float32))
        assert float(state.bits) == 0.0
        assert float(state.bits_down) == 0.0
        assert int(state.rounds) == 0
        assert len(losses) == T and np.isfinite(losses).all()


# ---------------------------------------------------------------------------
# trainer surface: preset:none pin, runtime parity, resume, S2 History
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trainer_problem():
    from repro.data import mnist_like, worker_batches
    from repro.models import softmax
    from repro.optim import inverse_time

    x, y = mnist_like(800, seed=0)
    cfg = softmax.SoftmaxConfig(l2=1.0 / len(x))
    params = softmax.init_params(jax.random.PRNGKey(0), cfg)

    def grad_fn(p, batch):
        return jax.value_and_grad(
            lambda pp: softmax.loss_fn(pp, batch, cfg)[0])(p)

    lr = inverse_time(xi=60.0, a=100.0)

    def mk_batches(Tt=48, seed=0):
        return worker_batches(x, y, R, 16, Tt, seed=seed)

    return grad_fn, params, lr, mk_batches


def _train(trainer_problem, **kw):
    from repro.train import RunConfig, train
    grad_fn, params, lr, mk_batches = trainer_problem
    run = RunConfig(total_steps=48, R=R, H=4, log_every=8, seed=0, **kw)
    return train(grad_fn, params, sgd(), ops.TopK(k=0.05), lr,
                 mk_batches(), run)


def test_trainer_preset_none_bit_exact(trainer_problem):
    st0, h0 = _train(trainer_problem)
    st1, h1 = _train(trainer_problem, faults="preset:none", fault_seed=3)
    np.testing.assert_array_equal(np.asarray(st0.master["x"]),
                                  np.asarray(st1.master["x"]))
    assert h0.loss == h1.loss
    assert h0.bits == h1.bits
    assert h0.rounds == h1.rounds


def test_trainer_fault_step_round_parity(trainer_problem):
    sts, hs = _train(trainer_problem, faults="preset:chaos",
                     runtime="step")
    str_, hr = _train(trainer_problem, faults="preset:chaos",
                      runtime="round")
    np.testing.assert_array_equal(np.asarray(sts.master["x"]),
                                  np.asarray(str_.master["x"]))
    np.testing.assert_array_equal(np.asarray(sts.inflight["x"]),
                                  np.asarray(str_.inflight["x"]))
    np.testing.assert_array_equal(np.asarray(sts.arrive_at),
                                  np.asarray(str_.arrive_at))
    assert hs.loss == hr.loss
    assert hs.bits == hr.bits


def test_trainer_crash_consistent_resume(tmp_path, trainer_problem):
    d = str(tmp_path / "ckpt")
    sta, _ = _train(trainer_problem, faults="preset:chaos", ckpt_dir=d,
                    ckpt_every=16)
    from repro.train import checkpoint as ckpt
    # wipe later snapshots so the resume starts mid-trajectory, with
    # payloads still in flight in the restored queue
    for dd in os.listdir(d):
        if dd.startswith("full_step_") and int(dd.rsplit("_", 1)[1]) > 16:
            shutil.rmtree(os.path.join(d, dd))
    full = ckpt.latest_full(d)
    assert full is not None and 0 < full < 48
    stb, _ = _train(trainer_problem, faults="preset:chaos", ckpt_dir=d,
                    ckpt_every=0, resume=True)
    for field in ("master", "memory", "inflight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sta, field)["x"]),
            np.asarray(getattr(stb, field)["x"]))
    np.testing.assert_array_equal(np.asarray(sta.arrive_at),
                                  np.asarray(stb.arrive_at))
    np.testing.assert_array_equal(np.asarray(sta.inflight_tau),
                                  np.asarray(stb.inflight_tau))


def test_trainer_dead_fleet_records_empty_rounds(trainer_problem):
    """S2 at the History level: scheduled rounds still close (and are
    recorded) when every worker is crashed — with zero payloads
    applied, zero bits, and the master untouched."""
    grad_fn, params, lr, mk_batches = trainer_problem
    dead = "crash=" + "+".join(f"{r}@0-64" for r in range(R))
    st, h = _train(trainer_problem, faults=dead)
    np.testing.assert_array_equal(np.asarray(st.master["x"]),
                                  np.asarray(params["x"]))
    assert h.bits[-1] == 0.0
    assert h.rounds[-1] == 0
    assert h.round_blocks, "scheduled rounds must still be recorded"
    assert all(n == 0 for (_, _, n) in h.round_blocks)


# ---------------------------------------------------------------------------
# distributed transports under faults (8 forced host devices)
# ---------------------------------------------------------------------------

DIST_COMMON = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.distributed import (make_dist_steps, make_dist_fault_steps,
                                    make_dist_fault_round, ShardCompressor)
from repro.core import engine, scenarios as scn, rounds as rnd, \
    schedule as sched
from repro.core.engine import stack_block
from repro.optim import sgd, constant

mesh = make_mesh((8,), ("data",))
R, d_in, d_out = 8, 16, 8
params = {"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}
specs = {"w": P(), "b": P()}
Wtrue = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

inner = sgd()
comp = ShardCompressor(mode="topk", k_frac=0.25)
T, H = 24, 3
mask = sched.async_schedule(T, R, H, seed=7)

def batches(seed=5):
    key = jax.random.PRNGKey(seed)
    out = []
    for t in range(T):
        key, s1 = jax.random.split(key)
        x = jax.random.normal(s1, (R, 8, d_in))
        out.append((x, jnp.einsum("rbi,io->rbo", x, Wtrue)))
    return out

def run_fault(wire, spec, sw="uniform"):
    tables = spec.tables(T, R)
    rows = engine.fault_rows(mask, tables, R)
    _, _, events = scn.fault_replay(mask, tables)
    init_fn, fls, fss = make_dist_fault_steps(
        grad_fn, inner, comp, constant(0.1), mesh, ("data",), specs,
        queue_depth=spec.depth, wire=wire, staleness_weight=sw)
    state = init_fn(params)
    jls, jss = jax.jit(fls), jax.jit(fss)
    key = jax.random.PRNGKey(1)
    for t, b in enumerate(batches()):
        key, sub = jax.random.split(key)
        row = engine.index_rows(rows, t)
        state, loss = (jss if events[t] else jls)(state, b, row, sub)
    return state

chaos = scn.FaultSpec(max_delay=3, drop=0.15, crash=((1, 4, 9),), seed=5)
"""

DIST_FAULT_PARITY = DIST_COMMON + r"""
# dense == sparse under chaos: states allclose, both bits ledgers exact
sd = run_fault("dense_psum", chaos)
ss = run_fault("sparse_allgather", chaos)
for f in ("master", "local", "memory"):
    np.testing.assert_allclose(
        np.asarray(getattr(sd, f)["w"]), np.asarray(getattr(ss, f)["w"]),
        rtol=1e-5, atol=1e-6)
np.testing.assert_array_equal(np.asarray(sd.bits), np.asarray(ss.bits))
np.testing.assert_array_equal(np.asarray(sd.bits_down),
                              np.asarray(ss.bits_down))
assert int(sd.rounds) == int(ss.rounds)

# trivial faults == the partial non-fault path (dense wire)
st = run_fault("dense_psum", scn.FaultSpec())
init_fn, lsn, ssn = make_dist_steps(
    grad_fn, inner, comp, constant(0.1), mesh, ("data",), specs,
    partial=True)
state = init_fn(params)
jl, js = jax.jit(lsn), jax.jit(ssn)
key = jax.random.PRNGKey(1)
for t, b in enumerate(batches()):
    key, sub = jax.random.split(key)
    if mask[t].any():
        state, _ = js(state, b, sub, jnp.asarray(mask[t]))
    else:
        state, _ = jl(state, b, sub)
np.testing.assert_allclose(np.asarray(st.master["w"]),
                           np.asarray(state.master["w"]),
                           rtol=1e-6, atol=1e-7)
np.testing.assert_array_equal(np.asarray(st.bits), np.asarray(state.bits))

# damped weighting: finite, and the two wires still agree
sdw = run_fault("dense_psum", chaos, sw="damped")
ssw = run_fault("sparse_allgather", chaos, sw="damped")
assert np.isfinite(np.asarray(sdw.master["w"])).all()
np.testing.assert_allclose(np.asarray(sdw.master["w"]),
                           np.asarray(ssw.master["w"]),
                           rtol=1e-5, atol=1e-6)
print("OK")
"""

DIST_FAULT_ROUNDS_AND_S2 = DIST_COMMON + r"""
def run_fault_rounds(wire, spec):
    tables = spec.tables(T, R)
    rows = engine.fault_rows(mask, tables, R)
    init_fn, round_fn = make_dist_fault_round(
        grad_fn, inner, comp, constant(0.1), mesh, ("data",), specs,
        queue_depth=spec.depth, wire=wire, staleness_weight="uniform")
    plans = rnd.compile_fault_rounds(mask, tables)
    bs = batches()
    state = init_fn(params)
    key = jax.random.PRNGKey(1)
    for p in plans:
        block = stack_block(bs[p.start:p.stop])
        rblock = engine.index_rows(rows, slice(p.start, p.stop))
        state, losses, key = round_fn(state, block, rblock, key)
    return state

for wire in ("dense_psum", "sparse_allgather"):
    sr = run_fault_rounds(wire, chaos)
    sp = run_fault(wire, chaos)
    np.testing.assert_array_equal(np.asarray(sr.master["w"]),
                                  np.asarray(sp.master["w"]))
    np.testing.assert_array_equal(np.asarray(sr.bits), np.asarray(sp.bits))
    assert int(sr.rounds) == int(sp.rounds)

# S2: an all-crashed fleet is a no-op on both transports
dead = scn.FaultSpec(crash=tuple((r, 0, T + 1) for r in range(R)))
for wire in ("dense_psum", "sparse_allgather"):
    s2 = run_fault(wire, dead)
    np.testing.assert_array_equal(np.asarray(s2.master["w"]),
                                  np.asarray(params["w"]))
    assert float(s2.bits) == 0.0 and float(s2.bits_down) == 0.0
    assert int(s2.rounds) == 0
print("OK")
"""


@pytest.mark.slow
def test_dist_fault_wire_parity(subproc):
    assert "OK" in subproc(DIST_FAULT_PARITY)


@pytest.mark.slow
def test_dist_fault_rounds_and_zero_support(subproc):
    assert "OK" in subproc(DIST_FAULT_ROUNDS_AND_S2)
