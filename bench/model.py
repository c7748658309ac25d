"""What the harness needs of a model configuration file: the program's
``ModelConfig`` built from it, and the weights made from ``--seed``.

A configuration file (``bench/configs/<name>.json``) holds the
published config's keys (``hidden_size``, ...), the keys cut to one
chip's share under ``reduced``, and under ``program`` the repo arch it
runs as with the depth and vocabulary set here.  The program's config
must carry the published widths: :func:`program_config` refuses one
that does not.

Weights are made here, not by the program: one jitted call from the
seed, on the device, in the configuration's dtype.  The reference makes
the same call to get the same values, so it takes nothing the program
made.  The tree has the layout the program's dense transformer takes
(``embed``, ``layers`` stacked on a leading layer axis, ``final_norm``,
``head``); the drivers check it against the program's own.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

#: the program's ModelConfig fields that hold a published width, and
#: the published key each must equal
WIDTHS = {"d_model": "hidden_size", "d_ff": "intermediate_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads"}

#: leaf order of the generated tree; a leaf's random stream is its
#: position here, so the values never depend on the program's layout
LEAVES = ("embed", "head", "final_norm", "layers/ln1", "layers/ln2",
          "layers/attn/wq", "layers/attn/wk", "layers/attn/wv",
          "layers/attn/wo", "layers/mlp/w1", "layers/mlp/w3",
          "layers/mlp/w2")


def program_config(conf: dict, smoke: bool = False):
    """The program's ModelConfig for a configuration file (``smoke``:
    the arch's CPU-sized config, for rehearsals only)."""
    from repro.configs import get_config
    prog = conf["program"]
    if smoke:
        cfg = get_config(prog["arch"], smoke=True)
        return dataclasses.replace(cfg, **conf.get("smoke", {}))
    cfg = get_config(prog["arch"])
    cfg = dataclasses.replace(
        cfg, n_layers=int(conf["num_hidden_layers"]),
        vocab=int(conf["vocab_size"]))
    for field, key in WIDTHS.items():
        if getattr(cfg, field) != conf[key]:
            raise ValueError(f"{prog['arch']}: program {field}="
                             f"{getattr(cfg, field)} but the published "
                             f"{key} is {conf[key]}")
    if cfg.hd != conf["hidden_size"] // conf["num_attention_heads"]:
        raise ValueError(f"{prog['arch']}: head size {cfg.hd} is not the "
                         f"published one")
    return cfg


def dims(cfg) -> dict:
    """Sizes the generator and the reference need, by plain names."""
    return {"L": cfg.n_layers, "d": cfg.d_model, "H": cfg.n_heads,
            "KV": cfg.n_kv_heads, "hd": cfg.hd, "ff": cfg.d_ff,
            "V": cfg.vocab, "theta": cfg.rope_theta,
            "dtype": cfg.param_dtype}


def leaf_shapes(dm: dict) -> dict:
    L, d, H, KV, hd, ff, V = (dm[k] for k in ("L", "d", "H", "KV", "hd",
                                              "ff", "V"))
    return {
        "embed": (V, d), "head": (d, V), "final_norm": (d,),
        "layers/ln1": (L, d), "layers/ln2": (L, d),
        "layers/attn/wq": (L, d, H * hd), "layers/attn/wk": (L, d, KV * hd),
        "layers/attn/wv": (L, d, KV * hd), "layers/attn/wo": (L, H * hd, d),
        "layers/mlp/w1": (L, d, ff), "layers/mlp/w3": (L, d, ff),
        "layers/mlp/w2": (L, ff, d),
    }


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf(key, i: int, path: str, shape: tuple, dtype):
    if path.endswith(("ln1", "ln2", "final_norm")):
        return jnp.zeros(shape, dtype)
    std = 1.0 if path == "embed" else shape[-2] ** -0.5
    z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (z * std).astype(dtype)


def make_weights(dm: dict, seed: int, out_shardings=None) -> dict:
    """The weight tree from ``seed`` in one jitted call: normal with
    standard deviation fan_in^-0.5 for projections, 1 for the embedding,
    zero norm offsets (the program's norms scale by 1 + w).
    ``out_shardings``: a flat {path: sharding} placing each leaf."""
    shapes = leaf_shapes(dm)
    dtype = jnp.dtype(dm["dtype"])

    def gen(key):
        return {path: _leaf(key, i, path, shapes[path], dtype)
                for i, path in enumerate(LEAVES)}

    shard = None if out_shardings is None else {p: out_shardings[p]
                                                for p in LEAVES}
    return nest(jax.jit(gen, out_shardings=shard)(seed_key(seed)))


@functools.lru_cache(maxsize=None)
def _leaf_fn(dm_items: tuple, path: str, sharding):
    dm = dict(dm_items)
    i = LEAVES.index(path)
    return jax.jit(lambda key: _leaf(key, i, path, leaf_shapes(dm)[path],
                                     jnp.dtype(dm["dtype"])),
                   out_shardings=sharding)


def make_leaf(dm: dict, seed: int, path: str, sharding=None):
    """One leaf of :func:`make_weights`, bit for bit, on its own."""
    return _leaf_fn(tuple(sorted(dm.items())), path, sharding)(
        seed_key(seed))


def check_layout(tree: dict, program_tree) -> None:
    """Refuse a generated tree whose paths or shapes differ from the
    program's parameter layout (``jax.eval_shape`` of its init)."""
    want = {k: tuple(v.shape) for k, v in flatten(program_tree).items()}
    have = {k: tuple(v.shape) for k, v in flatten(tree).items()}
    if want != have:
        raise ValueError(f"generated weights {have} do not match the "
                         f"program's layout {want}")
