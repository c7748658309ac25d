"""Plain reference of the Yi / llama decoder (arXiv:2403.04652).

Straight ``jax.numpy``: no kernels, no cache, no batching tricks, one
layer after the other.  Every product runs through the ``Numerics``
given, in float32 at ``Precision.HIGHEST`` for the reference
(:data:`F32`); the control computes the same in a lower precision
(:data:`FP8`).  Imports nothing of the program.

The block, as published: pre-norm RMSNorm, grouped-query attention with
rotary embeddings (rotate-half form, theta from the config), a SwiGLU
MLP, a final RMSNorm and an untied LM head.  Departures, each to match
what the benchmark states for the program:

- RMSNorm gains are stored as offsets ``w`` and applied as ``1 + w``
  (the weights are made with ``w = 0``, the published initial gain 1);
- the RMSNorm epsilon is 1e-6 where Yi's config states 1e-5 (the
  program's; at unit-scale activations the two differ by ~5e-6
  relative, far under every limit);
- the loss is the mean next-token cross-entropy over every position.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How the reference multiplies: ``cast`` rounds each operand of a
    product (identity for float32); the product itself is float32 at
    ``Precision.HIGHEST``."""
    name: str
    cast: Callable

    def dot(self, spec: str, a, b):
        return jnp.einsum(spec, self.cast(a.astype(jnp.float32)),
                          self.cast(b.astype(jnp.float32)),
                          precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _scaled(x, dtype):
    """``x`` rounded to ``dtype`` with one scale per tensor (its largest
    magnitude to the format's largest), back in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _fp8(x):
    """The usual fp8 training recipe: operands of a product in
    float8_e4m3fn, their gradients in float8_e5m2, each tensor with its
    own scale."""
    return _scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


F32 = Numerics("float32", lambda x: x)
FP8 = Numerics("fp8", _fp8)


def rmsnorm(x, w):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + w.astype(jnp.float32))


def rope(x, pos, theta):
    """x: [..., S, heads, hd]; pos: [..., S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(num: Numerics, q, k, v):
    """Causal grouped-query attention.  q: [B, S, H, hd]; k, v: [B, S,
    KV, hd] -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    q = q.reshape(B, S, KV, H // KV, hd) * hd ** -0.5
    s = num.dot("bqkgh,bskh->bkgqs", q, k)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = num.dot("bkgqs,bskh->bqkgh", p, v)
    return o.reshape(B, S, H, hd)


def layer(num: Numerics, dm: dict, h, lp: dict, pos):
    """One decoder layer on [B, S, d] activations."""
    B, S, _ = h.shape
    H, KV, hd = dm["H"], dm["KV"], dm["hd"]
    x = rmsnorm(h, lp["ln1"])
    a = lp["attn"]
    q = rope(num.dot("bsd,de->bse", x, a["wq"]).reshape(B, S, H, hd), pos,
             dm["theta"])
    k = rope(num.dot("bsd,de->bse", x, a["wk"]).reshape(B, S, KV, hd), pos,
             dm["theta"])
    v = num.dot("bsd,de->bse", x, a["wv"]).reshape(B, S, KV, hd)
    o = attention(num, q, k, v).reshape(B, S, H * hd)
    h = h + num.dot("bse,ed->bsd", o, a["wo"])
    x = rmsnorm(h, lp["ln2"])
    m = lp["mlp"]
    g = num.dot("bsd,df->bsf", x, m["w1"])
    u = num.dot("bsd,df->bsf", x, m["w3"])
    return h + num.dot("bsf,fd->bsd", jax.nn.silu(g) * u, m["w2"])


def hidden(num: Numerics, dm: dict, params: dict, tokens):
    """Final-normed hidden states [B, S, d] of ``tokens`` [B, S]."""
    B, S = tokens.shape
    # gathered from a float32 table, so the embedding's gradient sums
    # its rows in float32
    h = jnp.take(params["embed"].astype(jnp.float32), tokens, axis=0)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    for i in range(dm["L"]):
        lp = jax.tree_util.tree_map(lambda x: x[i], params["layers"])
        h = layer(num, dm, h, lp, pos)
    return rmsnorm(h, params["final_norm"])


def logits(num: Numerics, dm: dict, params: dict, tokens):
    """[B, S, V] float32 logits of ``tokens`` [B, S]."""
    return num.dot("bsd,dv->bsv", hidden(num, dm, params, tokens),
                   params["head"])


def loss(num: Numerics, dm: dict, params: dict, tokens, keep=None):
    """Mean next-token cross-entropy of ``tokens`` [B, S + 1].  ``keep``
    ([B, S] 0/1) weights the positions; None keeps every one."""
    lg = logits(num, dm, params, tokens[:, :-1])
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
    nll = lse - gold
    if keep is None:
        return jnp.mean(nll)
    return jnp.sum(nll * keep) / jnp.sum(keep)
