"""With the timed path broken underneath, a run's output check must come
out false: once for each fault a cell can have.  The harness's look for
a chip is skipped (CPU, smoke sizes, interpret-mode kernels); the rest
of a run is the benchmark's own."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import checks
from bench import run as brun

BENCH = brun.load_json(brun.CHECKOUT / "BENCHMARK.json")


def smoke(name):
    cell = brun.Cell(BENCH, name)
    cell.traffic = dict(cell.traffic, **cell.traffic["smoke"])
    return cell


def drive(cell, **hooks):
    driver = brun.load_module(brun.BENCH / "drivers" / f"{cell.driver}.py")
    run = driver.run(config=cell.config, traffic=cell.traffic, seed=11,
                     seconds=0.5, devices=jax.devices()[:cell.chips],
                     t0=time.perf_counter(), smoke=True, **hooks)
    return driver.verify(run, limits=cell.limits)


def unchanged(round_fn):
    """The round returns the state it was given."""
    def fn(state, block, key):
        copy = jax.tree_util.tree_map(jnp.copy, state)
        _, losses, key = round_fn(copy, block, key)
        return state, losses, key
    return fn


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_state_left_unchanged_fails(name):
    compared = drive(smoke(name), round_hook=unchanged)
    assert not checks.all_within(compared), compared
    assert compared["grad"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_half_batch_left_out_fails(name, monkeypatch):
    """The program's loss over the first half of each row only."""
    from repro.models import transformer
    orig = transformer.loss_fn

    def half(params, batch, cfg, policy, **kw):
        toks = batch["tokens"]
        keep = (toks.shape[1] - 1) // 2 + 1
        return orig(params, {**batch, "tokens": toks[:, :keep]}, cfg,
                    policy, **kw)

    monkeypatch.setattr(transformer, "loss_fn", half)
    compared = drive(smoke(name))
    assert not checks.all_within(compared), compared


def diverges_after(n):
    """Rounds after the first ``n`` report losses that are not finite."""
    def hook(round_fn):
        calls = [0]

        def fn(state, block, key):
            state, losses, key = round_fn(state, block, key)
            calls[0] += 1
            if calls[0] > n:
                losses = losses * jnp.nan
            return state, losses, key
        return fn
    return hook


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_nonfinite_round_in_window_fails(name):
    """The check rounds agree with the reference; a window round that
    diverged must still fail the run."""
    cell = smoke(name)
    t = cell.traffic
    checked = max(-(-int(t["check_steps"]) // int(t["H"])), 2)
    compared = drive(cell, round_hook=diverges_after(checked))
    assert compared["loss"]["value"] <= compared["loss"]["limit"]
    assert compared["nonfinite_rounds"]["value"] > 0
    assert not checks.all_within(compared), compared
