"""Fleet scenario simulator (DESIGN.md §8): mask-compilation
properties, aggregation-mode algebra (mean_R / mean_S /
support_weighted), dropped-worker state invariants, the
inject_dropout-vs-defer_sync differential failure-injection net, and
runtime x wire pinning — engine step/round on-process, the distributed
mesh paths in subprocesses.

Every property has a hypothesis version (skipped when hypothesis is
absent) AND a deterministic twin over ``strategies.SCENARIO_GRID`` /
``strategies.mask_grid()`` that runs everywhere.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import strategies
from hypothesis import given, settings

from repro.core import engine, operators as ops, policy as pol, \
    scenarios as scn, schedule as sched
from repro.optim import constant, sgd
from repro.train.trainer import RunConfig, train

R, D, LR = 4, 32, 0.05


# ---------------------------------------------------------------------------
# scenario -> mask compilation
# ---------------------------------------------------------------------------


def check_lossless_is_fixed_schedule(T, Rr, H):
    mask = scn.Scenario().mask(T, Rr, H=H)
    fixed = sched.fixed_schedule(T, H)
    np.testing.assert_array_equal(
        mask, np.broadcast_to(fixed[:, None], (T, Rr)))


@settings(max_examples=40, deadline=None)
@given(case=strategies.schedule_cases(max_T=120, max_R=8, max_H=10))
def test_lossless_scenario_is_fixed_schedule(case):
    T, Rr, H, _ = case
    check_lossless_is_fixed_schedule(T, Rr, H)


@pytest.mark.parametrize("T,Rr,H", [(1, 1, 1), (7, 3, 3), (24, 8, 5)])
def test_lossless_scenario_is_fixed_schedule_grid(T, Rr, H):
    check_lossless_is_fixed_schedule(T, Rr, H)


@settings(max_examples=40, deadline=None)
@given(sc=strategies.scenario_specs())
def test_scenario_mask_deterministic_and_bounded(sc):
    m1, m2 = sc.mask(30, 6, H=4), sc.mask(30, 6, H=4)
    np.testing.assert_array_equal(m1, m2)
    assert m1.shape == (30, 6) and m1.dtype == bool
    # every sync event survives thinning only: scenario masks are a
    # subset of the union of all per-worker base schedules
    assert m1.sum() <= 30 * 6


@pytest.mark.parametrize("i", range(len(strategies.SCENARIO_GRID)))
def test_scenario_grid_masks_deterministic(i):
    sc = strategies.SCENARIO_GRID[i]
    np.testing.assert_array_equal(sc.mask(24, 4, H=3), sc.mask(24, 4, H=3))


def test_scenario_thinning_is_monotone():
    """Each knob only removes sync events from the lossless schedule
    (for shared H): scenario masks are subsets of the base mask."""
    T, Rr, H = 36, 8, 4
    base = scn.Scenario().mask(T, Rr, H=H)
    for sc in [scn.Scenario(participation=0.5, seed=2),
               scn.Scenario(dropout_mid_round=0.4, seed=3),
               scn.Scenario(straggler_frac=0.5, seed=4),
               scn.Scenario(participation=0.7, dropout_mid_round=0.2,
                            straggler_frac=0.25, seed=5)]:
        m = sc.mask(T, Rr, H=H)
        assert not (m & ~base).any(), sc


def test_straggler_cadence():
    """A 100%-straggler fleet keeps exactly every k-th scheduled sync."""
    sc = scn.Scenario(straggler_frac=1.0, straggler_stale_rounds=3)
    m = sc.mask(36, 2, H=3)
    events = np.flatnonzero(sched.fixed_schedule(36, 3))
    kept = events[2::3]  # every 3rd of the 1-indexed event sequence
    for r in range(2):
        np.testing.assert_array_equal(np.flatnonzero(m[:, r]), kept)


def test_parse_roundtrip_and_presets():
    for sc in strategies.SCENARIO_GRID:
        assert scn.parse(sc.to_string() or "participation=1.0") == sc
    assert scn.parse("preset:flaky_fleet") is scn.PRESETS["flaky_fleet"]
    assert scn.parse(scn.PRESETS["dropout"]) is scn.PRESETS["dropout"]
    with pytest.raises(KeyError):
        scn.parse("preset:nope")
    with pytest.raises(KeyError):
        scn.parse("participaton=0.5")  # typo'd key
    with pytest.raises(ValueError):
        scn.parse("participation")
    with pytest.raises(ValueError):
        scn.Scenario(participation=1.5)


def test_mask_diagnostics():
    full = np.ones((8, 4), bool)
    assert not scn.is_partial(full)
    assert scn.participation_of(full) == 1.0
    part = full.copy()
    part[3, 2] = False
    assert scn.is_partial(part)
    assert 0.0 < scn.participation_of(part) < 1.0
    assert scn.participation_of(np.zeros((8, 4), bool)) == 0.0
    assert not scn.is_partial(sched.fixed_schedule(8, 2))  # [T] broadcasts


def test_warn_if_biased_once():
    part = np.ones((8, 4), bool)
    part[3, 2] = False
    pol._WARNED_KEYS.discard("scenario-mean_R-partial")
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        assert scn.warn_if_biased(part, "mean_R")
        assert scn.warn_if_biased(part, "mean_R")  # second time: silent
        assert not scn.warn_if_biased(part, "mean_S")
        assert not scn.warn_if_biased(np.ones((8, 4), bool), "mean_R")
    msgs = [w for w in wlog if "mean_R" in str(w.message)]
    assert len(msgs) == 1


# ---------------------------------------------------------------------------
# engine runs: shared harness
# ---------------------------------------------------------------------------


def _problem(T, Rr=R, seed=2, bounded=False):
    cs = jax.random.normal(jax.random.PRNGKey(1), (Rr, D))

    def grad_fn(params, data):
        c, noise = data
        err = params["w"] - c
        g = jnp.tanh(err) if bounded else err + 0.01 * noise
        return 0.5 * jnp.sum(err ** 2), {"w": g}

    k = jax.random.PRNGKey(seed)
    bs = []
    for _ in range(T):
        k, s = jax.random.split(k)
        bs.append((cs, jax.random.normal(s, (Rr, D))))
    return grad_fn, bs


def _run(mask, aggregate, operator=None, runtime="step", Rr=R, T=None,
         bounded=False, prefix=None):
    T = T if T is not None else np.asarray(mask).shape[0]
    operator = operator if operator is not None else ops.TopK(k=8)
    grad_fn, bs = _problem(T, Rr=Rr, bounded=bounded)
    if prefix is not None:
        bs, mask = bs[:prefix], np.asarray(mask)[:prefix]
    params = {"w": jnp.zeros(D)}
    inner = sgd()
    state = engine.init(params, inner, Rr)
    key = jax.random.PRNGKey(3)
    if runtime == "round":
        sstep = engine.make_superstep(grad_fn, inner, operator, constant(LR),
                                      Rr, global_rounds=True,
                                      aggregate=aggregate)
        return engine.run_rounds(state, sstep, bs, mask, key)
    step = engine.make_step(grad_fn, inner, operator, constant(LR), Rr,
                            global_rounds=True, aggregate=aggregate)
    return engine.run(state, step, bs, mask, key)


def _assert_state_equal(s1, s2):
    for f in s1._fields:
        a, b = getattr(s1, f), getattr(s2, f)
        if a is None:
            assert b is None, f
            continue
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f)


# ---------------------------------------------------------------------------
# aggregation-mode algebra
# ---------------------------------------------------------------------------


def check_mean_S_equals_mean_R_at_full_participation(mask):
    """With every scheduled sync an all-agree row, |S| = R: the two
    division rules are the same operation, bit for bit."""
    s1, l1 = _run(mask, "mean_R")
    s2, l2 = _run(mask, "mean_S")
    _assert_state_equal(s1, s2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def check_support_weighted_identity_equals_mean_S(mask):
    """Identity compression: every syncing worker supports every
    coordinate, so the per-coordinate survivor count is exactly |S|."""
    s1, l1 = _run(mask, "mean_S", operator=ops.Identity())
    s2, l2 = _run(mask, "support_weighted", operator=ops.Identity())
    _assert_state_equal(s1, s2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@settings(max_examples=10, deadline=None)
@given(case=strategies.fixed_schedule_cases(max_T=20, max_H=6))
def test_mean_S_equals_mean_R_full_participation(case):
    T, H = case
    check_mean_S_equals_mean_R_at_full_participation(
        sched.fixed_schedule(T, H))


@settings(max_examples=10, deadline=None)
@given(mask=strategies.sync_masks(max_T=16, max_R=R))
def test_support_weighted_identity_equals_mean_S(mask):
    if mask.shape[1] != R:
        mask = np.broadcast_to(mask.any(axis=1)[:, None],
                               (mask.shape[0], R)).copy()
    check_support_weighted_identity_equals_mean_S(mask)


@pytest.mark.parametrize("name,mask", strategies.mask_grid(T=16, R=R, H=4))
def test_aggregate_algebra_grid(name, mask):
    if not scn.is_partial(mask):
        check_mean_S_equals_mean_R_at_full_participation(mask)
    check_support_weighted_identity_equals_mean_S(mask)


def test_support_weighted_zero_support_keeps_master():
    """When every syncing worker's top-k payload misses a coordinate,
    the numerator is exactly 0 and max(count, 1) keeps the master
    value there — no NaN, no drift."""
    T = 4
    mask = np.zeros((T, R), bool)
    mask[-1] = True

    def grad_fn(params, data):
        # only coordinate 0 carries signal: k=1 topk payloads all pick
        # it, so coordinates 1..D-1 have zero support at the sync
        g = jnp.zeros(D).at[0].set(1.0)
        return jnp.sum(params["w"] ** 2), {"w": g}

    inner = sgd()
    state = engine.init({"w": jnp.ones(D)}, inner, R)
    step = engine.make_step(grad_fn, inner, ops.TopK(k=1), constant(LR), R,
                            global_rounds=True,
                            aggregate="support_weighted")
    bs = [(jnp.zeros(R),)] * T
    state, _ = engine.run(state, step, bs, mask, jax.random.PRNGKey(0))
    w = np.asarray(state.master["w"])
    assert np.isfinite(w).all()
    np.testing.assert_array_equal(w[1:], np.ones(D - 1))  # untouched
    assert w[0] < 1.0                                     # updated


# ---------------------------------------------------------------------------
# dropped-worker state invariants
# ---------------------------------------------------------------------------


def check_never_syncing_worker(mask, worker):
    """A worker whose column is all-False never touches the master and
    is never touched by it: its view stays the initial master, its
    error memory never activates, its local iterate free-runs."""
    mask = np.array(mask, bool, copy=True)
    mask[:, worker] = False
    state, _ = _run(mask, "mean_S")
    view = np.asarray(state.master_view["w"][worker])
    np.testing.assert_array_equal(view, np.zeros(D, np.float32))
    np.testing.assert_array_equal(
        np.asarray(state.memory["w"][worker]), np.zeros(D, np.float32))
    if mask.any():
        other = int(np.flatnonzero(mask.any(axis=0))[0])
        assert not np.array_equal(np.asarray(state.local["w"][worker]),
                                  np.asarray(state.local["w"][other]))


def test_never_syncing_worker_grid():
    base = np.broadcast_to(
        sched.fixed_schedule(16, 4)[:, None], (16, R)).copy()
    check_never_syncing_worker(base, worker=2)


def test_all_false_mask_master_untouched():
    state, _ = _run(np.zeros((10, R), bool), "mean_S")
    np.testing.assert_array_equal(np.asarray(state.master["w"]),
                                  np.zeros(D, np.float32))
    assert float(state.bits) == 0.0 and int(state.rounds) == 0


def check_memory_growth_linear(k_stale, H=2, T=None):
    """Straggler error memory is at most linear in missed rounds: with
    per-coordinate gradients bounded by 1 (tanh) and lr fixed, the
    half-vector a straggler accumulates over a gap of g steps has norm
    A <= lr * g * sqrt(D).  Top-k (delta = k/D) contracts each banked
    residual by c = sqrt(1 - delta), so the memory recursion
    ||M'|| <= c (||M|| + A) stays below cA/(1-c) — linear in the gap,
    for any number of syncs (Lemma 4's bounded-memory argument)."""
    T = T if T is not None else 8 * k_stale * H
    sc = scn.Scenario(straggler_frac=1.0, straggler_stale_rounds=k_stale)
    mask = sc.mask(T, R, H=H)
    state, _ = _run(mask, "mean_S", bounded=True, T=T)
    gaps = sched.worker_gaps(mask) or [T]
    g_max = max(gaps)
    c = np.sqrt(1.0 - 8 / D)  # _run compresses with TopK(k=8)
    bound = (c / (1.0 - c)) * LR * g_max * np.sqrt(D) * (1.0 + 1e-6)
    norms = np.linalg.norm(np.asarray(state.memory["w"]), axis=-1)
    assert (norms <= bound).all(), (norms, bound)
    return float(norms.max())


@pytest.mark.parametrize("k_stale", [1, 2, 4])
def test_straggler_memory_linear_in_staleness(k_stale):
    check_memory_growth_linear(k_stale)


# ---------------------------------------------------------------------------
# failure-injection differential: inject_dropout vs defer_sync
# ---------------------------------------------------------------------------


def test_inject_vs_defer_divergence_confined():
    """The same failure injected at two layers — payload lost
    (inject_dropout) vs payload arrives stale (defer_sync) — produces
    trajectories that are bit-identical until the stale arrival; after
    it, divergence is confined to the master and the deferred worker's
    state until the other workers' next sync round.  This is the
    regression net for the async/stale-sync regime of
    core/async_qsparse.py."""
    T, H, w = 16, 4, 1
    base = np.broadcast_to(
        sched.fixed_schedule(T, H)[:, None], (T, R)).copy()
    t0, later = H - 1, H + 1          # sync at t=4; stale arrival at t=6
    next_sync = 2 * H - 1             # the fleet's next round at t=8
    m_drop = scn.inject_dropout(base, w, t0)
    m_defer = scn.defer_sync(base, w, t0, later)
    np.testing.assert_array_equal(m_drop[:later], m_defer[:later])

    # bit-identical through every step before the stale arrival
    s1, l1 = _run(m_drop, "mean_S", prefix=later)
    s2, l2 = _run(m_defer, "mean_S", prefix=later)
    _assert_state_equal(s1, s2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))

    # after the arrival, before the fleet's next round: only the master
    # and worker w's state may differ — nobody else has read the master
    s1, _ = _run(m_drop, "mean_S", prefix=next_sync)
    s2, _ = _run(m_defer, "mean_S", prefix=next_sync)
    assert not np.array_equal(np.asarray(s1.master["w"]),
                              np.asarray(s2.master["w"]))
    for f in ("local", "memory", "master_view"):
        a = np.asarray(getattr(s1, f)["w"])
        b = np.asarray(getattr(s2, f)["w"])
        for r in range(R):
            if r == w:
                continue
            np.testing.assert_array_equal(a[r], b[r], err_msg=f"{f}[{r}]")
    # worker w's state does differ (it banked/spent its payload)
    assert not np.array_equal(np.asarray(s1.local["w"][w]),
                              np.asarray(s2.local["w"][w])) or \
        not np.array_equal(np.asarray(s1.memory["w"][w]),
                           np.asarray(s2.memory["w"][w]))

    # at the fleet's next sync the master difference propagates to all
    s1, _ = _run(m_drop, "mean_S", prefix=next_sync + 1)
    s2, _ = _run(m_defer, "mean_S", prefix=next_sync + 1)
    for r in range(R):
        assert not np.array_equal(
            np.asarray(s1.master_view["w"][r]),
            np.asarray(s2.master_view["w"][r])), r


def test_injection_helpers_validate():
    base = np.broadcast_to(
        sched.fixed_schedule(8, 4)[:, None], (8, R)).copy()
    with pytest.raises(ValueError):
        scn.inject_dropout(base, 0, 0)   # no sync scheduled at t=0
    with pytest.raises(ValueError):
        scn.defer_sync(base, 0, 3, 2)    # later must follow step
    m = scn.defer_sync(base, 0, 3, 5)
    assert not m[3, 0] and m[5, 0] and base[3, 0] and not base[5, 0]


# ---------------------------------------------------------------------------
# runtime x path pinning
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("aggregate", list(scn.AGGREGATES))
@pytest.mark.parametrize("name,mask", strategies.mask_grid(T=16, R=R, H=4))
def test_step_round_parity_scenarios(aggregate, name, mask):
    """Round-program runtime == per-step runtime, bit for bit, on every
    scenario mask family x aggregation mode."""
    s1, l1 = _run(mask, aggregate, runtime="step")
    s2, l2 = _run(mask, aggregate, runtime="round")
    _assert_state_equal(s1, s2)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


@pytest.mark.slow
@pytest.mark.parametrize("aggregate", ["mean_S", "support_weighted"])
def test_dist_wire_parity_partial(subproc, aggregate):
    """dense_psum and sparse_allgather agree on partial masks (states,
    exact bit ledgers, round counts), and the partial round program
    matches the per-step path bit-for-bit — on a real 8-way mesh."""
    subproc(_DIST_CODE.format(aggregate=aggregate), devices=8)


_DIST_CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
from repro.core.distributed import make_dist_steps, make_dist_round, \
    ShardCompressor
from repro.optim import sgd, constant

mesh = make_mesh((8,), ("data",))
R, d_in, d_out, T, H = 8, 12, 6, 12, 4
params = {{"w": jnp.zeros((d_in, d_out)), "b": jnp.zeros((d_out,))}}
specs = {{"w": P(None, None), "b": P(None)}}
params = jax.device_put(params, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs,
    is_leaf=lambda z: isinstance(z, P)))
Wt = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))

def grad_fn(p, batch):
    x, y = batch
    f = lambda pp: jnp.mean((x @ pp["w"] + pp["b"] - y) ** 2)
    return jax.value_and_grad(f)(p)

bs = []
key = jax.random.PRNGKey(7)
for _ in range(T):
    key, s = jax.random.split(key)
    x = jax.random.normal(s, (R, 8, d_in))
    bs.append((x, jnp.einsum("rbi,io->rbo", x, Wt)))

mask = np.ones((T, R), bool)
mask[3, 2] = False
mask[7, :] = False
mask[7, 0] = True

def run(wire):
    comp = ShardCompressor("topk", 0.25)
    init_fn, ls_, ss_ = make_dist_steps(
        grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
        wire=wire, aggregate="{aggregate}", partial=True)
    st = init_fn(params)
    ls, ss = jax.jit(ls_), jax.jit(ss_)
    k = jax.random.PRNGKey(1)
    for t in range(T):
        k, sub = jax.random.split(k)
        if (t + 1) % H == 0:
            st, _ = ss(st, bs[t], sub, mask[t])
        else:
            st, _ = ls(st, bs[t], sub)
    return jax.device_get(st)

sd, sp = run("dense_psum"), run("sparse_allgather")
for f in ("master", "local", "memory", "view"):
    a, b = getattr(sd, f), getattr(sp, f)
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]),
                               rtol=1e-5, atol=1e-6, err_msg=f)
assert float(sd.bits) == float(sp.bits)
assert int(sd.rounds) == int(sp.rounds) == T // H

comp = ShardCompressor("topk", 0.25)
init_fn, round_fn = make_dist_round(
    grad_fn, sgd(), comp, constant(0.1), mesh, ("data",), specs,
    wire="dense_psum", aggregate="{aggregate}", partial=True)
st2 = init_fn(params)
k = jax.random.PRNGKey(1)
for r0 in range(0, T, H):
    blk = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *bs[r0:r0 + H])
    st2, _, k = round_fn(st2, blk, mask[r0 + H - 1], k)
st2 = jax.device_get(st2)
np.testing.assert_array_equal(np.asarray(sd.master["w"]),
                              np.asarray(st2.master["w"]))
assert float(sd.bits) == float(st2.bits)
assert int(sd.rounds) == int(st2.rounds)
print("DIST SCENARIO PARITY OK")
"""


# ---------------------------------------------------------------------------
# trainer wiring
# ---------------------------------------------------------------------------


def test_trainer_scenario_run():
    T = 12
    grad_fn, bs = _problem(T)
    run = RunConfig(total_steps=T, R=R, H=4, policy="topk:k=8",
                    scenario="participation=0.6,seed=3",
                    aggregate="mean_S", log_every=4)
    state, hist = train(grad_fn, {"w": jnp.zeros(D)}, sgd(), None,
                        constant(LR), bs, run)
    assert np.isfinite(np.asarray(state.master["w"])).all()
    assert hist.loss


def test_trainer_scenario_rejects_async():
    run = RunConfig(total_steps=4, R=R, scenario="preset:dropout",
                    asynchronous=True)
    with pytest.raises(ValueError, match="scenario"):
        train(lambda p, b: (0.0, p), {"w": jnp.zeros(D)}, sgd(),
              ops.TopK(k=8), constant(LR), [], run)


def test_trainer_scenario_mean_R_warns():
    T = 8
    grad_fn, bs = _problem(T)
    pol._WARNED_KEYS.discard("scenario-mean_R-partial")
    run = RunConfig(total_steps=T, R=R, H=4, policy="topk:k=8",
                    scenario="participation=0.4,seed=5")
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        train(grad_fn, {"w": jnp.zeros(D)}, sgd(), None, constant(LR),
              bs, run)
    assert any("mean_R" in str(w.message) for w in wlog)


# ---------------------------------------------------------------------------
# fleet scale (pytest -m scenarios lane)
# ---------------------------------------------------------------------------


@pytest.mark.scenarios
def test_fleet_scale_mask_statistics():
    """R = 1024: the realized participation rate concentrates near the
    spec's survival probability p * (1 - dropout)."""
    sc = scn.Scenario(participation=0.8, dropout_mid_round=0.1, seed=9)
    mask = sc.mask(40, 1024, H=4)
    p_hat = scn.participation_of(mask)
    assert abs(p_hat - 0.8 * 0.9) < 0.03
    assert scn.is_partial(mask)


@pytest.mark.scenarios
def test_fleet_scale_engine_run():
    """R = 256 through the vmapped engine on a flaky fleet: finite
    state, loss decreased, ledgers consistent with the mask."""
    Rr, T, H = 256, 8, 2
    sc = scn.PRESETS["flaky_fleet"]
    mask = sc.mask(T, Rr, H=H)
    state, losses = _run(mask, "support_weighted", Rr=Rr, T=T)
    assert np.isfinite(np.asarray(state.master["w"])).all()
    assert float(losses[-1]) < float(losses[0])
    assert int(state.rounds) == int(mask.any(axis=1).sum())


@pytest.mark.scenarios
def test_fleet_scale_sharded_worker_axis(subproc):
    """R = 1024 sharded over an 8-way mesh via shard_worker_axis: the
    partitioned run stays finite and syncs the fleet."""
    subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine, operators as ops, scenarios as scn
from repro.launch.mesh import make_mesh
from repro.optim import constant, sgd

mesh = make_mesh((8,), ("data",))
Rr, D, T, H = 1024, 16, 4, 2
mask = scn.PRESETS["flaky_fleet"].mask(T, Rr, H=H)

def grad_fn(p, data):
    err = p["w"] - data
    return 0.5 * jnp.sum(err ** 2), {"w": err}

inner = sgd()
state = engine.init({"w": jnp.zeros(D)}, inner, Rr)
state = engine.shard_worker_axis(state, mesh)
step = engine.make_step(grad_fn, inner, ops.TopK(k=4), constant(0.05),
                        Rr, global_rounds=True, aggregate="mean_S")
bs = [jnp.ones((Rr, D)) for _ in range(T)]
state, losses = engine.run(state, step, bs, mask, jax.random.PRNGKey(0))
assert np.isfinite(np.asarray(state.master["w"])).all()
assert int(state.rounds) == int(mask.any(axis=1).sum())
print("FLEET SHARDED OK", float(losses[-1]))
""", devices=8)
