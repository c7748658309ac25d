"""Plain reference of Qsparse-local-SGD training (the paper's
Algorithm 1 with a fixed sync period H), for the training cells.

R workers each take local momentum-SGD steps on their own batches; at
every H-th step each worker compresses its error-compensated
accumulator ``mem + master - local`` with Top_k, keeps the rest in its
error memory, and the master moves by the mean of the R compressed
updates, which every worker then takes as its local iterate.

Top_k is taken per row of each leaf along its hidden axis
(:data:`ROW_AXIS`): the k = round(k_frac * row) largest magnitudes of
every row survive (ties at the k-th magnitude all survive).  Parameters,
their gradients and the momentum live in the configuration's parameter
dtype, the error memory in float32; every operation computes in
float32 (``llama.F32``) or in the control's lower precision.

Worker r lives on ``devices[r % len(devices)]``; the update is summed
leaf by leaf on the first device, so a 4-worker run fits four chips.
Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import model as bmodel
from bench.reference import llama

#: the axis each leaf's Top_k rows run along: the hidden (d_model) axis
ROW_AXIS = {"embed": 1, "head": 0, "final_norm": 0, "layers/ln1": 1,
            "layers/ln2": 1, "layers/attn/wq": 1, "layers/attn/wk": 1,
            "layers/attn/wv": 1, "layers/attn/wo": 2, "layers/mlp/w1": 1,
            "layers/mlp/w3": 1, "layers/mlp/w2": 2}

F32 = jnp.float32


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up to ``lr`` over ``warmup`` steps, then constant."""
    if step < opt["warmup"]:
        return opt["lr"] * (step + 1) / opt["warmup"]
    return opt["lr"]


def topk_rows(acc, axis: int, k_frac: float):
    """Keep the k largest magnitudes of every row along ``axis``."""
    n = acc.shape[axis]
    k = max(1, min(n, int(round(k_frac * n))))
    a = jnp.abs(jnp.moveaxis(acc, axis, -1))
    kth = jax.lax.top_k(a, k)[0][..., -1:]
    keep = (a >= kth) & (a > 0)
    return jnp.moveaxis(jnp.where(keep, jnp.moveaxis(acc, axis, -1), 0.0),
                        -1, axis)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _grad_step(num, dm_items, local, mu, tokens, keep, momentum):
    """The loss and the momentum after one more gradient."""
    dm = dict(dm_items)
    loss, g = jax.value_and_grad(
        lambda p: llama.loss(num, dm, p, tokens, keep))(local)
    mu = jax.tree_util.tree_map(
        lambda m, gg: (momentum * m.astype(F32) + gg.astype(F32)
                       ).astype(m.dtype), mu, g)
    return loss, mu


@functools.partial(jax.jit, donate_argnums=(0,))
def _half_step(local, mu, lr):
    """The local iterate after a step, stored in the parameter dtype."""
    return jax.tree_util.tree_map(
        lambda p, m: (p.astype(F32) - lr * m.astype(F32)).astype(p.dtype),
        local, mu)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _compress(axis, k_frac, mem, master, local, mu, lr):
    """Top_k of the error-compensated accumulator at a sync step.  The
    sync step's half-step iterate ``local - lr * mu`` is never stored,
    so it is not rounded to the parameter dtype."""
    half = local.astype(F32) - lr * mu.astype(F32)
    acc = mem + master.astype(F32) - half
    sel = topk_rows(acc, axis, k_frac)
    return sel, acc - sel


@jax.jit
def _apply(master, total, R):
    return (master.astype(F32) - total / R).astype(master.dtype)


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))


def _copy_to(tree, device):
    return jax.tree_util.tree_map(
        lambda x: jnp.copy(jax.device_put(x, device)), tree)


def follow(dm: dict, seed: int, blocks, opt: dict, *, steps: int,
           num=llama.F32, devices=None, fault: str | None = None) -> dict:
    """Train from the weights of ``seed`` for ``steps`` steps and record
    what the output check compares.

    ``blocks``: host token arrays [H, R, b, S + 1], one per round, in
    the order the program ran them.  ``opt``: ``lr``, ``warmup``,
    ``momentum``, ``H``, ``k_frac``.  ``fault`` plants one of the faults
    the check must catch: ``half_batch`` (the loss of each row over its
    first half only).

    Returns ``losses`` (mean over workers, per step), ``grad`` (per
    leaf, the norm over workers of the momentum after the first round:
    the first gradient where H = 1) and ``update`` (per leaf, the norm
    of the master's change after ``steps``).
    """
    devices = devices or [jax.devices()[0]]
    H = int(opt["H"])
    R = blocks[0].shape[1]
    dev = [devices[r % len(devices)] for r in range(R)]
    dm_items = tuple(sorted(dm.items()))
    master = bmodel.make_weights(dm, seed)
    master = jax.device_put(master, dev[0])
    local = [_copy_to(master, dev[r]) for r in range(R)]
    mu = [jax.tree_util.tree_map(jnp.zeros_like, local[r]) for r in range(R)]
    mem = [None] * R
    losses, grad = [], None
    for t in range(steps):
        block = blocks[t // H]
        lr = lr_at(t, opt)
        step_losses = []
        for r in range(R):
            tokens = jax.device_put(jnp.asarray(block[t % H, r]), dev[r])
            keep = None
            if fault == "half_batch":
                S = tokens.shape[1] - 1
                keep = jax.device_put(
                    jnp.broadcast_to(jnp.arange(S) < S // 2,
                                     (tokens.shape[0], S)).astype(F32),
                    dev[r])
            loss, mu[r] = _grad_step(num, dm_items, local[r], mu[r],
                                     tokens, keep,
                                     jnp.float32(opt["momentum"]))
            step_losses.append(loss)
        # read only once every worker's step is dispatched, so the
        # workers' devices run at once
        losses.append(float(np.mean([float(x) for x in step_losses])))
        if (t + 1) % H:
            local = [_half_step(local[r], mu[r], jnp.float32(lr))
                     for r in range(R)]
            continue
        flat_master = bmodel.flatten(master)
        flat_local = [bmodel.flatten(x) for x in local]
        flat_mu = [bmodel.flatten(x) for x in mu]
        new_mem = [dict() for _ in range(R)]
        new_master = {}
        for path, m_leaf in flat_master.items():
            total = None
            for r in range(R):
                m_r = jax.device_put(m_leaf, dev[r])
                old = (mem[r][path] if mem[r] is not None
                       else jnp.zeros(m_leaf.shape, F32, device=dev[r]))
                sel, new_mem[r][path] = _compress(
                    ROW_AXIS[path], float(opt["k_frac"]), old, m_r,
                    flat_local[r][path], flat_mu[r][path], jnp.float32(lr))
                sel = jax.device_put(sel, dev[0])
                total = sel if total is None else total + sel
            new_master[path] = _apply(m_leaf, total, jnp.float32(R))
        flat_local = flat_mu = None
        mem = new_mem
        master = bmodel.nest(new_master)
        local = [_copy_to(master, dev[r]) for r in range(R)]
        if grad is None:
            grad = {}
            for path in flat_master:
                sq = sum(float(_norm(bmodel.flatten(mu[r])[path])) ** 2
                         for r in range(R))
                grad[path] = sq ** 0.5
    local = mu = mem = None
    master0 = bmodel.flatten(jax.device_put(bmodel.make_weights(dm, seed),
                                            dev[0]))
    flat_master = bmodel.flatten(master)
    update = {p: float(_norm(flat_master[p].astype(F32)
                             - master0[p].astype(F32)))
              for p in flat_master}
    return {"losses": losses, "grad": grad, "update": update}
