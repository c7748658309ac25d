"""The control, at a size a test run holds: the plain reference in fp8
(one precision step below the configuration's bf16) put in the
program's place must read far above the program against the reference
in float32; and the planted faults of the training cells must fail the
cells' committed limits."""

import time

import jax
import pytest

from bench import checks
from bench import control
from bench import run as brun

BENCH = brun.load_json(brun.CHECKOUT / "BENCHMARK.json")


class SmokeCell(brun.Cell):
    def __init__(self, name):
        super().__init__(BENCH, name)
        self.traffic = dict(self.traffic, **self.traffic["smoke"])


def smoke_training(cell, seed, variant):
    return control.training(cell, seed, variant, jax.devices()[:1],
                            smoke=True)


def program_numbers(cell, seed):
    driver = brun.load_module(brun.BENCH / "drivers" / "train.py")
    run = driver.run(config=cell.config, traffic=cell.traffic, seed=seed,
                     seconds=0.3, devices=jax.devices()[:1],
                     t0=time.perf_counter(), smoke=True)
    return {k: c["value"] for k, c in
            driver.verify(run, limits=cell.limits).items()}


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_training_control_reads_far_above_the_program(name):
    """At smoke size in float32 the program matches the reference to
    round-off, so the cells' limits (set at the published sizes on the
    chip) do not apply; the control must still read far above it."""
    cell = SmokeCell(name)
    own = program_numbers(cell, 3)
    ctl = smoke_training(cell, 3, "control")
    assert max(ctl[k] / max(own[k], 1e-12) for k in ctl) > 100, (own, ctl)


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_training_half_batch_fails(name):
    cell = SmokeCell(name)
    compared = checks.with_limits(smoke_training(cell, 3, "half_batch"),
                                  cell.limits)
    assert not checks.all_within(compared), compared
