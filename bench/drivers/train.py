"""Training cells: the window drives the round program of
``core.distributed.make_dist_round``, built with the library calls
``launch/train.py`` makes (program config, ``get_model``,
``param_specs``, ``activation_policy``, ``ShardCompressor.from_spec``,
``make_dist_round``) on a ``launch.mesh`` mesh of the cell's chips.  The launcher's own
host loop (logging, per-step bit reads) is not what is timed.

Set-up makes the weights on the device from the seed, the token blocks
once (``data.LMTokenStream``, host time not timed), builds the round
program and its state, and drives that same program through its first
rounds, which compile and are what the output check compares.  The
window then runs whole rounds, each ending in ``block_until_ready``,
until ``seconds`` have passed; ``train_tokens_per_s`` is the tokens of
all workers in every round completed with finite losses, over the
window.  A round whose losses are not finite trained nothing: it counts
as ``failed``, and the output check holds the count to 0.

Traffic keys: ``mesh`` ([data, model]), ``seq``, ``batch`` (per
worker), ``H`` (local steps per round, the last one syncs), ``policy``
(a ``core.policy`` spec), ``wire``, ``dispatch``, ``lr``, ``warmup``,
``momentum``, ``blocks`` (token blocks made and cycled), ``check_steps``
(steps the reference follows, rounded up to whole rounds).
"""

from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import checks, work
from bench import model as bmodel
from bench.runs import CompileCounter, Run


def token_blocks(vocab: int, R: int, traffic: dict, seed: int):
    """``blocks`` host arrays [H, R, batch, seq + 1] from the seed."""
    from repro.data import LMTokenStream
    rng = np.random.default_rng(seed)
    s_chain, s_draw = (int(x) for x in rng.integers(0, 2**31 - 1, 2))
    H, n = int(traffic["H"]), int(traffic["blocks"])
    stream = LMTokenStream(vocab=vocab, R=R, order=64, seed=s_chain)
    steps = list(stream.batches(int(traffic["batch"]), int(traffic["seq"]),
                                H * n, seed=s_draw))
    return [np.stack([s["tokens"] for s in steps[i * H:(i + 1) * H]])
            for i in range(n)]


@jax.jit
def _leaf_norms(tree):
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in tree.items()}


@jax.jit
def _change_norm(new, old):
    return jnp.sqrt(jnp.sum(jnp.square(new.astype(jnp.float32)
                                       - old.astype(jnp.float32))))


def change_norms(master: dict, dm: dict, seed: int) -> dict:
    """Per leaf, the norm of the master's change from the weights of
    ``seed``, made again leaf by leaf (no copy of them is kept)."""
    out = {}
    for p, x in bmodel.flatten(master).items():
        x0 = bmodel.make_leaf(dm, seed, p, x.sharding)
        out[p] = float(_change_norm(x, x0))
        del x0
    return out


def program(cfg, traffic: dict, mesh, params):
    """The round program and its init, built as ``launch/train.py``
    builds them; ``params`` may be shapes (compile rehearsals)."""
    from repro.core.distributed import ShardCompressor, make_dist_round
    from repro.models import get_model
    from repro.optim import momentum_sgd, warmup_piecewise
    from repro.sharding.specs import activation_policy, param_specs
    daxes = ("data",)
    model = get_model(cfg)
    policy = activation_policy(cfg, for_serving=False, data_axes=daxes)
    specs = param_specs(cfg)

    def grad_fn(params, batch):
        def loss(p):
            return model.loss_fn(p, batch, cfg, policy)[0]
        return jax.value_and_grad(loss)(params)

    uplink = ShardCompressor.from_spec(traffic["policy"], params,
                                       dispatch=traffic["dispatch"])
    return make_dist_round(
        grad_fn, momentum_sgd(float(traffic["momentum"])), uplink,
        warmup_piecewise(float(traffic["lr"]), int(traffic["warmup"]),
                         [1 << 30]),
        mesh, daxes, specs, wire=traffic["wire"])


def shardings(cfg, mesh) -> dict:
    """Each weight leaf's placement on the mesh, by its path."""
    from repro.sharding.specs import param_specs, sanitize_spec
    shapes = bmodel.leaf_shapes(bmodel.dims(cfg))
    return {p: NamedSharding(mesh, sanitize_spec(sp, shapes[p], mesh))
            for p, sp in bmodel.flatten(param_specs(cfg)).items()}


def build(config: dict, traffic: dict, seed: int, devices, smoke: bool):
    """The round program, its state and the token blocks, as set-up
    makes them."""
    from repro.launch.mesh import make_mesh
    from repro.models import get_model
    cfg = bmodel.program_config(config, smoke=smoke)
    dm = bmodel.dims(cfg)
    mesh = make_mesh(traffic["mesh"], ("data", "model"))
    if list(mesh.devices.flat) != list(devices):
        raise ValueError(f"the mesh holds {list(mesh.devices.flat)}, the "
                         f"cell runs on {list(devices)}")
    R = int(traffic["mesh"][0])
    params = bmodel.make_weights(dm, seed, out_shardings=shardings(cfg, mesh))
    bmodel.check_layout(params, jax.eval_shape(
        lambda: get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)))
    init_fn, round_fn = program(cfg, traffic, mesh, params)
    state = init_fn(params)
    del params
    host_blocks = token_blocks(cfg.vocab, R, traffic, seed)
    block_sharding = NamedSharding(mesh, P(None, "data"))
    blocks = [jax.device_put({"tokens": b}, block_sharding)
              for b in host_blocks]
    key = jax.device_put(bmodel.seed_key(seed), NamedSharding(mesh, P()))
    return dict(cfg=cfg, dm=dm, R=R, state=state, round_fn=round_fn,
                blocks=blocks, host_blocks=host_blocks, key=key)


def run(*, config: dict, traffic: dict, seed: int, seconds: float,
        devices, t0: float, trace_dir=None, smoke: bool = False,
        round_hook=None) -> Run:
    """Set up, check-rounds, window.  ``round_hook`` wraps the round
    program (fault tests plant faults there)."""
    compiles = CompileCounter()
    b = build(config, traffic, seed, devices, smoke)
    state, round_fn, blocks, key = (b["state"], b["round_fn"], b["blocks"],
                                    b["key"])
    if round_hook is not None:
        round_fn = round_hook(round_fn)
    H, R = int(traffic["H"]), b["R"]
    n_check = math.ceil(int(traffic["check_steps"]) / H)
    if n_check >= len(blocks):
        raise ValueError("blocks must outnumber the check rounds")
    losses, grad, update = [], None, None
    i = 0
    # the check rounds: the window's own program and feed, from the seed
    for i in range(max(n_check, 2)):
        state, out, key = round_fn(state, blocks[i % len(blocks)], key)
        if i < n_check:
            losses += [float(x) for x in np.asarray(out)]
        if i == 0:
            grad = {p: float(v) for p, v in _leaf_norms(
                bmodel.flatten(state.inner["mu"])).items()}
        if i == n_check - 1:
            update = change_norms(state.master, b["dm"], seed)
    jax.block_until_ready(state)
    i += 1
    compiled_before = compiles.n
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    t_start = time.perf_counter()
    rounds = failed = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.round"):
            state, out, key = round_fn(state, blocks[i % len(blocks)], key)
            out = np.asarray(jax.block_until_ready(out))
        rounds += 1
        failed += int(not np.all(np.isfinite(out)))
        i += 1
        now = time.perf_counter()
        if now - t_start >= seconds:
            break
    window = now - t_start
    if trace_dir is not None:
        jax.profiler.stop_trace()
    in_window = compiles.n - compiled_before
    print(f"bench: {in_window} compilations inside the window",
          file=sys.stderr, flush=True)
    seq, batch = int(traffic["seq"]), int(traffic["batch"])
    tokens = (rounds - failed) * H * R * batch * seq
    del state, blocks, round_fn, b["state"], b["blocks"], b["round_fn"]
    dm = b["dm"]
    facts = {
        "kind": "train", "rounds": rounds, "steps": rounds * H,
        "syncs": rounds, "workers": R, "window_host_s": window,
        "tokens": tokens, "compiles_in_window": in_window,
        "flops_per_token": work.train_flops_per_token(dm, seq),
        "compress_bytes_per_sync": work.compress_bytes(dm, traffic),
        "wire": traffic["wire"],
    }
    return Run(
        attempted=rounds, failed=failed,
        metrics={"train_tokens_per_s": tokens / window,
                 "setup_s": t_start - t0},
        facts=facts,
        check={"dm": dm, "seed": seed, "traffic": traffic,
               "blocks": b["host_blocks"][:n_check],
               "steps": n_check * H, "devices": list(devices),
               "program": {"losses": losses, "grad": grad,
                           "update": update}})


def reference(check: dict, num=None, fault=None) -> dict:
    """The plain reference's record for a run's check."""
    from bench.reference import llama, qsparse
    t = check["traffic"]
    opt = {"lr": float(t["lr"]), "warmup": int(t["warmup"]),
           "momentum": float(t["momentum"]), "H": int(t["H"]),
           "k_frac": work.topk_fraction(t["policy"])}
    return qsparse.follow(check["dm"], check["seed"], check["blocks"], opt,
                          steps=check["steps"], num=num or llama.F32,
                          devices=check["devices"], fault=fault)


def verify(run: Run, *, limits: dict) -> dict:
    """Run the reference over the check steps and compare; the window's
    rounds with losses that are not finite are held to none."""
    ref = reference(run.check)
    numbers = checks.training_numbers(run.check["program"], ref)
    numbers["nonfinite_rounds"] = run.failed
    return checks.with_limits(numbers, limits)
