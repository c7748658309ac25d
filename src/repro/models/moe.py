"""Mixture-of-Experts decoder (llama4-maverick, qwen3-moe).

Expert parallelism: experts are sharded over the 'model' mesh axis.  The
MoE FFN is computed inside a nested ``shard_map`` manual over that axis:
each shard routes the (replicated) token activations to its *local*
experts through fixed-capacity buffers (sort-based position assignment,
overflow drops counted), runs a grouped dense einsum over local experts,
and the shards' partial outputs are combined with one ``psum`` — the
same wire class as a tensor-parallel MLP, with no flop-polluting
one-hot dispatch einsum (see DESIGN.md §4).

llama4-maverick: interleaved FFN (every ``moe_interleave``-th layer is
MoE, others dense) + a shared expert added to the routed output, top-1
routing.  qwen3: every layer MoE, top-8.
"""

from __future__ import annotations

import math

import numpy as np
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, NO_SHARDING, ShardingPolicy
from repro.models.layers import (
    attn_block_decode,
    attn_block_train,
    attn_params,
    cache_prefill,
    dense_init,
    embed,
    init_kv_cache,
    maybe_shard,
    mlp_params,
    norm_params,
    rmsnorm,
    swiglu,
)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _expert_params(key, cfg: ModelConfig, stacked: int | None):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    pre = (stacked,) if stacked else ()
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], pre + (d, E), jnp.float32),
        "w1": dense_init(ks[1], pre + (E, d, ff), cfg.pdtype),
        "w3": dense_init(ks[2], pre + (E, d, ff), cfg.pdtype),
        "w2": dense_init(ks[3], pre + (E, ff, d), cfg.pdtype),
    }
    if cfg.shared_expert:
        p["shared"] = mlp_params(ks[4], cfg, stacked, d_ff=cfg.dense_ff)
    return p


def _is_moe_layer(i: int, cfg: ModelConfig) -> bool:
    return (i + 1) % cfg.moe_interleave == 0


def init_params(key: jax.Array, cfg: ModelConfig):
    L, P_ = cfg.n_layers, cfg.moe_interleave
    assert L % P_ == 0, "n_layers must divide by moe_interleave"
    nper = L // P_
    ks = jax.random.split(key, 8)
    layers = {
        # all-layer stacks, reshaped to [nper, P_, ...] at scan time
        "ln1": norm_params(cfg, L),
        "attn": attn_params(ks[0], cfg, L),
        "ln2": norm_params(cfg, L),
        "moe": _expert_params(ks[1], cfg, nper),
    }
    if P_ > 1:
        layers["dense_mlp"] = mlp_params(ks[2], cfg, L - nper,
                                         d_ff=cfg.dense_ff)
    params = {
        "embed": dense_init(ks[3], (cfg.vocab, cfg.d_model), cfg.pdtype, scale=1.0),
        "layers": layers,
        "final_norm": norm_params(cfg, None),
        "head": dense_init(ks[4], (cfg.d_model, cfg.vocab), cfg.pdtype),
    }
    return params


# ---------------------------------------------------------------------------
# routed FFN
# ---------------------------------------------------------------------------


def _capacity(n_assign: int, E: int, cf: float) -> int:
    return max(4, int(math.ceil(n_assign / E * cf)))


def _route(x2d: jnp.ndarray, router: jnp.ndarray, cfg: ModelConfig):
    """x2d: [T, d].  Returns (eids [T,K], weights [T,K], aux_loss)."""
    logits = (x2d.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, eids = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.clip(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # switch-style load-balance aux
    E = router.shape[-1]
    frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(eids, E, dtype=jnp.float32), axis=1), axis=0
    )
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return eids, w.astype(jnp.float32), aux


def _assignments(eids, e_start, E_loc: int, C: int):
    """Shared routing bookkeeping: per-assignment local-expert id,
    capacity position, keep mask (sort-based position assignment)."""
    T, K = eids.shape
    A = T * K
    flat_e = eids.reshape(A)
    tok_of = jnp.repeat(jnp.arange(T), K)
    local = (flat_e >= e_start) & (flat_e < e_start + E_loc)
    le = jnp.where(local, flat_e - e_start, E_loc)  # E_loc = overflow bucket
    order = jnp.argsort(le, stable=True)
    le_sorted = le[order]
    start_of = jnp.searchsorted(le_sorted, jnp.arange(E_loc + 1))
    pos_sorted = jnp.arange(A) - start_of[le_sorted]
    pos = jnp.zeros(A, jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = local & (pos < C)
    le_c = jnp.where(keep, le, 0).astype(jnp.int32)
    pos_c = jnp.where(keep, pos, 0).astype(jnp.int32)
    return tok_of, local, keep, le_c, pos_c


def _expert_compute_local(
    x2d: jnp.ndarray,              # [T, d] tokens (replicated across EP shards)
    eids: jnp.ndarray,             # [T, K]
    weights: jnp.ndarray,          # [T, K]
    w1, w3, w2,                    # local expert stacks [E_loc, ...]
    e_start, E_loc: int, C: int,
    shard_axis: str | None = None,
):
    """Contribution of experts [e_start, e_start+E_loc) to every token.
    Returns ([T, d] partial output, dropped_assignments).

    ``shard_axis``: when running in XLA-auto mode (no nested shard_map),
    constrain the [E, C, *] buffers to shard over that mesh axis so the
    grouped einsums stay expert-parallel instead of replicating 100GB+
    expert stacks.
    """
    T, K = eids.shape
    d = x2d.shape[-1]
    A = T * K
    flat_w = weights.reshape(A)
    tok_of, local, keep, le, pos = _assignments(eids, e_start, E_loc, C)

    def eshard(t):
        if shard_axis is None:
            return t
        return maybe_shard(t, P(shard_axis, *([None] * (t.ndim - 1))))

    buf = jnp.zeros((E_loc, C, d), x2d.dtype)
    buf = buf.at[le, pos].add(
        jnp.where(keep[:, None], x2d[tok_of], 0))
    buf = eshard(buf)

    h = jnp.einsum("ecd,edf->ecf", buf, w1, preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", buf, w3, preferred_element_type=jnp.float32)
    h = eshard((jax.nn.silu(h) * g).astype(x2d.dtype))
    o = jnp.einsum("ecf,efd->ecd", h, w2, preferred_element_type=jnp.float32)
    o = eshard(o)

    contrib = o[le, pos] * jnp.where(keep, flat_w, 0.0)[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tok_of].add(contrib)
    dropped = jnp.sum(local & ~keep)
    return out, dropped


def _expert_bwd_local(x2d, eids, weights, w1, w3, w2, e_start, E_loc, C,
                      dout):
    """Hand-written VJP of ``_expert_compute_local`` (this shard's
    contribution).  Recomputes the forward residuals from the inputs so
    nothing is checkpointed across the shard boundary.

    Returns (dx2d_partial, dweights_partial, dw1, dw3, dw2)."""
    T, K = eids.shape
    d = x2d.shape[-1]
    A = T * K
    flat_w = weights.reshape(A)
    tok_of, local, keep, le, pos = _assignments(eids, e_start, E_loc, C)

    # ---- recompute forward intermediates
    buf = jnp.zeros((E_loc, C, d), x2d.dtype)
    buf = buf.at[le, pos].add(jnp.where(keep[:, None], x2d[tok_of], 0))
    pre1 = jnp.einsum("ecd,edf->ecf", buf, w1,
                      preferred_element_type=jnp.float32)
    pre3 = jnp.einsum("ecd,edf->ecf", buf, w3,
                      preferred_element_type=jnp.float32)
    sig = jax.nn.sigmoid(pre1)
    silu1 = pre1 * sig
    h = (silu1 * pre3).astype(x2d.dtype)
    o = jnp.einsum("ecf,efd->ecd", h, w2,
                   preferred_element_type=jnp.float32)

    # ---- backward
    dcontrib = dout[tok_of]                                   # [A, d]
    wk = jnp.where(keep, flat_w, 0.0)
    # d(weights): contrib = o[le, pos] * w  =>  dw = <dout, o[le, pos]>
    dflat_w = jnp.sum(dcontrib * o[le, pos], axis=-1) * keep
    dweights = dflat_w.reshape(T, K)
    # d(o): scatter dout * w into slots
    do = jnp.zeros((E_loc, C, d), jnp.float32)
    do = do.at[le, pos].add(dcontrib.astype(jnp.float32) * wk[:, None])
    dh = jnp.einsum("ecd,efd->ecf", do, w2.astype(jnp.float32))
    dw2 = jnp.einsum("ecf,ecd->efd", h.astype(jnp.float32), do)
    dsilu1 = dh * pre3
    dpre3 = dh * silu1
    dpre1 = dsilu1 * (sig * (1 + pre1 * (1 - sig)))
    dbuf = (jnp.einsum("ecf,edf->ecd", dpre1, w1.astype(jnp.float32))
            + jnp.einsum("ecf,edf->ecd", dpre3, w3.astype(jnp.float32)))
    bw = buf.astype(jnp.float32)
    dw1 = jnp.einsum("ecd,ecf->edf", bw, dpre1)
    dw3 = jnp.einsum("ecd,ecf->edf", bw, dpre3)
    # d(x2d): gather dbuf back through the scatter
    dx_assign = dbuf[le, pos] * keep[:, None]
    dx2d = jnp.zeros((T, d), jnp.float32).at[tok_of].add(dx_assign)
    return dx2d, dweights, dw1, dw3, dw2


import functools


@functools.lru_cache(maxsize=64)
def _make_ep_apply(axis: str, E: int, C: int, nshards: int):
    """Expert-parallel apply with a hand-written VJP: both the forward
    and the backward run inside a nested shard_map manual over ``axis``
    (experts sharded), sidestepping JAX's unsupported AD-through-nested-
    shard_map path.  The expert-id offset comes from an arange operand
    ``er`` (no axis_index => no ambiguous PartitionId in SPMD lowering).

    Cached at module level with no traced closures (tracer-leak safe);
    the mesh is taken from the ambient context at call time.
    """
    E_loc = E // nshards

    def fwd_shard(x2d, eids, wts, w1, w3, w2, er):
        out, dropped = _expert_compute_local(
            x2d, eids, wts, w1, w3, w2, er[0], E_loc, C)
        return jax.lax.psum(out, axis), jax.lax.psum(dropped, axis)

    def bwd_shard(x2d, eids, wts, w1, w3, w2, er, dout):
        dx, dwts, dw1, dw3, dw2 = _expert_bwd_local(
            x2d, eids, wts, w1, w3, w2, er[0], E_loc, C, dout)
        return (jax.lax.psum(dx, axis), jax.lax.psum(dwts, axis),
                dw1, dw3, dw2)

    def _fwd_mapped(x2d, eids, wts, w1, w3, w2, er):
        mesh = jax.sharding.get_abstract_mesh()
        return jax.shard_map(
            fwd_shard, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P()), axis_names={axis}, check_vma=True,
        )(x2d, eids, wts, w1, w3, w2, er)

    def _bwd_mapped(x2d, eids, wts, w1, w3, w2, er, dout):
        mesh = jax.sharding.get_abstract_mesh()
        return jax.shard_map(
            bwd_shard, mesh=mesh,
            in_specs=(P(), P(), P(), P(axis), P(axis), P(axis), P(axis),
                      P()),
            out_specs=(P(), P(), P(axis), P(axis), P(axis)),
            axis_names={axis}, check_vma=True,
        )(x2d, eids, wts, w1, w3, w2, er, dout)

    @jax.custom_vjp
    def apply(x2d, eids, wts, w1, w3, w2, er):
        return _fwd_mapped(x2d, eids, wts, w1, w3, w2, er)

    def apply_fwd(x2d, eids, wts, w1, w3, w2, er):
        out = _fwd_mapped(x2d, eids, wts, w1, w3, w2, er)
        return out, (x2d, eids, wts, w1, w3, w2, er)

    def apply_bwd(res, cts):
        x2d, eids, wts, w1, w3, w2, er = res
        dout, _ = cts  # the dropped-count output carries no cotangent
        dx, dwts, dw1, dw3, dw2 = _bwd_mapped(
            x2d, eids, wts, w1, w3, w2, er, jnp.asarray(dout, jnp.float32))
        f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
        return (dx.astype(x2d.dtype), f0(eids), dwts.astype(wts.dtype),
                dw1.astype(w1.dtype), dw3.astype(w3.dtype),
                dw2.astype(w2.dtype), f0(er))

    apply.defvjp(apply_fwd, apply_bwd)
    return apply


def moe_ffn(x: jnp.ndarray, mp, cfg: ModelConfig,
            policy: ShardingPolicy = NO_SHARDING):
    """x: [B, S, d] -> ([B, S, d], aux_metrics dict)."""
    B, S, d = x.shape
    if policy.ep_axis is not None:
        # tokens must be replicated across the EP axis at the shard_map
        # boundary (seq-sharded activations would force an illegal
        # Manual/Auto mixed spec); this is the EP all-gather.
        x = maybe_shard(x, P(None, None, None))
    x2d = x.reshape(B * S, d)
    eids, w, aux = _route(x2d, mp["router"], cfg)
    T = B * S
    E = cfg.n_experts
    C = _capacity(T * cfg.moe_top_k, E, cfg.capacity_factor)

    if policy.ep_axis is not None and not policy.vary_axes:
        # serving path (plain jit): explicit EP via nested shard_map
        nshards = jax.sharding.get_abstract_mesh().shape[policy.ep_axis]
        apply = _make_ep_apply(policy.ep_axis, E, C, nshards)
        out, dropped = apply(x2d, eids, w, mp["w1"], mp["w3"], mp["w2"],
                             jnp.arange(E))
    else:
        # training path (inside the manual-(pod,data) region): XLA-auto
        # expert parallelism with explicit [E, C, *] buffer constraints
        # (AD through a nested shard_map is unsupported in current JAX;
        # see DESIGN.md §4 and the custom_vjp note above).
        out, dropped = _expert_compute_local(
            x2d, eids, w, mp["w1"], mp["w3"], mp["w2"], 0, E, C,
            shard_axis=policy.ep_axis,
        )

    out = out.astype(x.dtype)
    if cfg.shared_expert:
        out = out + swiglu(x2d, mp["shared"])
    metrics = {"aux_loss": aux, "dropped": dropped.astype(jnp.float32)}
    return out.reshape(B, S, d), metrics


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------


def _reshape_period(tree, nper: int, P_: int):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((nper, P_) + x.shape[1:]), tree
    )


def apply_stack(params, h, positions, cfg: ModelConfig,
                policy: ShardingPolicy, collect_kv: bool = False):
    """Scan over periods of ``moe_interleave`` layers (last layer of each
    period is MoE; the preceding ones use the dense FFN stack)."""
    L, P_ = cfg.n_layers, cfg.moe_interleave
    nper = L // P_
    lay = params["layers"]
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32).reshape(nper, P_)
    attn = _reshape_period(lay["attn"], nper, P_)
    ln1 = lay["ln1"].reshape(nper, P_, -1)
    ln2 = lay["ln2"].reshape(nper, P_, -1)
    if P_ > 1:
        dense_mlp = _reshape_period(lay["dense_mlp"], nper, P_ - 1)
    moe_p = lay["moe"]

    def body(carry, xs):
        h = carry
        attn_p, l1, l2, wins, moe_lp = xs[:5]
        dense_lp = xs[5] if P_ > 1 else None
        kvs = []
        for j in range(P_):
            lp_attn = jax.tree_util.tree_map(lambda x: x[j], attn_p)
            a, kv = attn_block_train(rmsnorm(h, l1[j]), lp_attn, cfg,
                                     wins[j], positions, policy)
            h = h + a
            hn = rmsnorm(h, l2[j])
            if j == P_ - 1:
                f, metrics = moe_ffn(hn, moe_lp, cfg, policy)
            else:
                lp_mlp = jax.tree_util.tree_map(lambda x: x[j], dense_lp)
                f = swiglu(hn, lp_mlp)
                metrics = None
            h = h + f
            h = maybe_shard(h, policy.act)
            kvs.append(kv)
        aux = metrics["aux_loss"]
        dropped = metrics["dropped"]
        ys = (kvs if collect_kv else None, aux, dropped)
        return h, ys

    body_fn = jax.checkpoint(body) if cfg.remat else body
    xs = (attn, ln1, ln2, windows, moe_p)
    if P_ > 1:
        xs = xs + (dense_mlp,)
    h, (kvs, aux, dropped) = jax.lax.scan(body_fn, h, xs)
    metrics = {"aux_loss": jnp.mean(aux), "dropped": jnp.sum(dropped)}
    return h, kvs, metrics


def loss_fn(params, batch, cfg: ModelConfig,
            policy: ShardingPolicy = NO_SHARDING, loss_chunk: int = 1024):
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    h = embed(inp, params["embed"]).astype(cfg.adtype)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, _, metrics = apply_stack(params, h, positions, cfg, policy)
    h = rmsnorm(h, params["final_norm"])
    W = params["head"]
    c = min(loss_chunk, S)
    pad = (-S) % c
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)))
    msk = jnp.pad(jnp.ones((B, S), jnp.float32), ((0, 0), (0, pad)))
    n = hp.shape[1] // c
    hp = hp.reshape(B, n, c, -1).swapaxes(0, 1)
    lp = lp.reshape(B, n, c).swapaxes(0, 1)
    msk = msk.reshape(B, n, c).swapaxes(0, 1)

    def chunk_loss(carry, xs):
        hc, lc, mc = xs
        logits = (hc @ W).astype(jnp.float32)
        logits = maybe_shard(logits, policy.logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - gold) * mc), None

    from repro.models.layers import pvary
    total, _ = jax.lax.scan(chunk_loss,
                            pvary(jnp.zeros((), jnp.float32),
                                  policy.vary_axes), (hp, lp, msk))
    loss = total / (B * S) + cfg.router_aux_weight * metrics["aux_loss"]
    return loss, {"aux_loss": metrics["aux_loss"], "dropped": metrics["dropped"]}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    wins = cfg.layer_windows()
    return init_kv_cache(cfg, batch, wins[0], max_len, stacked=cfg.n_layers)


def prefill(params, batch, cfg: ModelConfig,
            policy: ShardingPolicy = NO_SHARDING, max_len: Optional[int] = None):
    tokens = batch["tokens"]
    h = embed(tokens, params["embed"]).astype(cfg.adtype)
    B, S, _ = h.shape
    max_len = max_len or max(cfg.max_seq_len, S)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, kvs, _ = apply_stack(params, h, positions, cfg, policy, collect_kv=True)
    hl = rmsnorm(h[:, -1:], params["final_norm"])
    logits = (hl @ params["head"]).astype(jnp.float32)
    # kvs: list (per j in period) of (k, v) stacked [nper, B, S, KV, hd]
    L, P_ = cfg.n_layers, cfg.moe_interleave
    nper = L // P_
    k_all = jnp.stack([kvs[j][0] for j in range(P_)], axis=1).reshape(
        (L,) + kvs[0][0].shape[1:]
    )
    v_all = jnp.stack([kvs[j][1] for j in range(P_)], axis=1).reshape(
        (L,) + kvs[0][1].shape[1:]
    )
    cache = init_cache(cfg, B, max_len)
    cache = jax.vmap(lambda cc, k, v: cache_prefill(cc, k, v, S))(cache, k_all, v_all)
    return logits, cache, S


def decode_step(params, cache, token, pos, cfg: ModelConfig,
                policy: ShardingPolicy = NO_SHARDING):
    h = embed(token[:, None], params["embed"]).astype(cfg.adtype)
    L, P_ = cfg.n_layers, cfg.moe_interleave
    nper = L // P_
    lay = params["layers"]
    wins = cfg.layer_windows()

    def get(tree, i):
        return jax.tree_util.tree_map(lambda x: x[i], tree)

    dense_idx = 0
    new_cache_layers = []
    cache_list = [get(cache, i) for i in range(L)]
    for i in range(L):
        lp_attn = get(lay["attn"], i)
        a, c = attn_block_decode(rmsnorm(h, lay["ln1"][i]), lp_attn, cfg,
                                 cache_list[i], pos, wins[i])
        h = h + a
        hn = rmsnorm(h, lay["ln2"][i])
        if _is_moe_layer(i, cfg):
            f, _ = moe_ffn(hn, get(lay["moe"], i // P_), cfg, policy)
        else:
            f = swiglu(hn, get(lay["dense_mlp"], dense_idx))
            dense_idx += 1
        h = h + f
        new_cache_layers.append(c)
    new_cache = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *new_cache_layers
    )
    h = rmsnorm(h, params["final_norm"])
    logits = (h[:, 0] @ params["head"]).astype(jnp.float32)
    return maybe_shard(logits, policy.logits), new_cache
