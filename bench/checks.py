"""The output check's comparisons: each number compared, beside its
limit from ``bench/limits/<cell>.json``.

Training compares, against the plain reference over the first steps:

- ``loss``: the largest relative gap of a step's loss;
- ``grad``: per leaf, the gap between the program's norm of the
  momentum after the first round (the first gradient where H = 1) and
  the reference's, over the larger of the reference's norm of that leaf
  and of the median leaf; the worst leaf;
- ``update``: the same for the master's change after the check steps,
  over the leaves whose reference gradient is at least a thousandth of
  the median leaf's (a leaf the reference barely moves moves by
  round-off alone);
- ``nonfinite_rounds``: the window's rounds whose losses are not
  finite (limit 0: a diverged round trained nothing).
"""

from __future__ import annotations

import math

import numpy as np


def leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    med = float(np.median([ref[p] for p in ref]))
    gaps = [abs(prog[p] - ref[p]) / max(ref[p], med) for p in leaves]
    return float(np.max(gaps))


def moved_leaves(ref_grad: dict) -> list:
    med = float(np.median(list(ref_grad.values())))
    return sorted(p for p, v in ref_grad.items() if v >= 1e-3 * med)


def training_numbers(prog: dict, ref: dict) -> dict:
    n = len(ref["losses"])
    if len(prog["losses"]) != n:
        raise ValueError(f"program ran {len(prog['losses'])} check steps, "
                         f"the reference {n}")
    p, r = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    return {"loss": float(np.max(np.abs(p - r) / np.abs(r))),
            "grad": leaf_gap(prog["grad"], ref["grad"]),
            "update": leaf_gap(prog["update"], ref["update"],
                               moved_leaves(ref["grad"]))}


def with_limits(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}


def all_within(compared: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
