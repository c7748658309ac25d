"""Fleet scenario lab (DESIGN.md §8): convergence vs participation and
throughput vs fleet size.

Two row families:

* ``scenario/convergence/p<pct>`` — the convex softmax problem under a
  participation-p fleet with support_weighted aggregation: final loss /
  eval error / uplink bits as participation drops 1.0 -> 0.5.  The
  p100 row runs the lossless scenario and doubles as the bit-for-bit
  anchor (it is the plain synchronous schedule).
* ``scenario/steps_per_s/R<R>`` — synthetic-quadratic engine throughput
  as the worker axis grows 8 -> 1024 (the vmapped fleet;
  ``engine.shard_worker_axis`` spreads the same axis over a mesh when
  more than one device is present).

Both families land in ``BENCH_scenarios.json`` and are gated by
``check_regression.py`` like every other suite.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BenchRow, convex_problem
from repro.core import engine, operators as ops, scenarios as scn
from repro.data import worker_batches
from repro.launch.mesh import make_mesh
from repro.optim import constant, inverse_time, sgd
from repro.train import RunConfig, train

T_CONV = 300
K = 40 / 7850.0


def _convergence(participation, seed=0, R=15, b=8, H=4):
    x, y, cfg, params, grad_fn, eval_fn = convex_problem()
    sc = scn.Scenario(participation=participation, seed=seed + 1)
    run_cfg = RunConfig(total_steps=T_CONV, R=R, H=H, log_every=25,
                        seed=seed, eval_every=0,
                        scenario=sc, aggregate="support_weighted")
    batches = worker_batches(x, y, R, b, T_CONV, seed=seed)
    op = ops.QuantizedSparsifier(k=K, s=15)
    t0 = time.time()
    state, hist = train(grad_fn, params, sgd(),
                        op, inverse_time(xi=60.0, a=100.0), batches,
                        run_cfg)
    wall = time.time() - t0
    metrics = eval_fn(state.master)
    mask = sc.mask(T_CONV, R, H=H)
    return {
        "final_loss": hist.loss[-1],
        "eval_error": float(metrics["error"]),
        "bits": hist.bits[-1],
        "p_hat": scn.participation_of(mask),
        "us_per_step": wall / T_CONV * 1e6,
    }


def _steps_per_s(R, D=2048, T=16, warmup=4):
    sc = scn.PRESETS["flaky_fleet"]
    mask = sc.mask(T + warmup, R, H=4)

    def grad_fn(p, data):
        err = p["w"] - data
        return 0.5 * jnp.sum(err ** 2), {"w": err}

    inner = sgd()
    state = engine.init({"w": jnp.zeros(D)}, inner, R)
    if len(jax.devices()) > 1 and R % len(jax.devices()) == 0:
        mesh = make_mesh((len(jax.devices()),), ("data",))
        state = engine.shard_worker_axis(state, mesh)
    step = engine.make_step(grad_fn, inner, ops.TopK(k=0.05), constant(0.05),
                            R, global_rounds=True,
                            aggregate="support_weighted")
    bs = [jnp.ones((R, D)) for _ in range(T + warmup)]
    key = jax.random.PRNGKey(0)
    state, _ = engine.run(state, step, bs[:warmup], mask[:warmup], key)
    jax.block_until_ready(state.master["w"])
    t0 = time.time()
    state, _ = engine.run(state, step, bs[warmup:], mask[warmup:], key)
    jax.block_until_ready(state.master["w"])
    wall = time.time() - t0
    return {"us_per_step": wall / T * 1e6,
            "steps_per_s": T / wall,
            "bits": float(state.bits)}


def run():
    rows = []
    for pct in (100, 80, 50):
        r = _convergence(pct / 100.0)
        rows.append(BenchRow(
            f"scenario/convergence/p{pct}", r["us_per_step"],
            f"loss={r['final_loss']:.3f};err={r['eval_error']:.3f};"
            f"bits={r['bits']:.3g};p_hat={r['p_hat']:.2f}",
            wire_bits=None))
    for R in (8, 64, 256, 1024):
        r = _steps_per_s(R)
        # exact-k topk on a deterministic mask: the uplink ledger is
        # machine-independent — gate it as wire_bits
        rows.append(BenchRow(
            f"scenario/steps_per_s/R{R}", r["us_per_step"],
            f"steps_per_s={r['steps_per_s']:.1f};bits={r['bits']:.3g}",
            wire_bits=r["bits"]))
    return rows
