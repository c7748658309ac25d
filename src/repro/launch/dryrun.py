import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers and compiles for the production mesh, and extract the
roofline terms from the compiled artifact.

The two lines above MUST run before any other import (jax locks the
device count at first init).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, per-collective byte totals and the
derived roofline terms (see benchmarks/roofline.py for the report).
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCHS,
    INPUT_SHAPES,
    get_config,
    shape_supported,
)
from repro.core.distributed import ShardCompressor, make_dist_steps
from repro.launch.mesh import data_axes, make_production_mesh, worker_count
from repro.models import get_model
from repro.optim import constant, momentum_sgd
from repro.sharding.specs import (activation_policy, param_specs,
                                  sanitize_spec)

ART_DIR = "artifacts/dryrun"

# v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

from repro.launch.roofline_parse import collective_bytes  # noqa: E402


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------


def _sds(shape, dtype, mesh=None, spec=None):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec or P()))


def _combine_first(spec, daxes):
    """Prefix a spec's axis-0 entry with the data axes (ZeRO/worker dim)."""
    entries = tuple(spec) if spec is not None else ()
    first = entries[0] if entries else None
    rest = entries[1:] if entries else ()
    if first is None:
        return P(tuple(daxes), *rest)
    firsts = first if isinstance(first, tuple) else (first,)
    return P(tuple(daxes) + tuple(firsts), *rest)


def abstract_params(cfg, mesh, model):
    """ShapeDtypeStructs for params with their NamedShardings."""
    sds = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(cfg)
    is_spec = lambda z: isinstance(z, P) or z is None

    def attach(s, spec):
        return _sds(s.shape, s.dtype, mesh, sanitize_spec(spec, s.shape, mesh))

    return jax.tree_util.tree_map(attach, sds, specs, is_leaf=lambda z: False), specs


def input_specs(cfg, shape_name: str, mesh, *, for_train: bool):
    """Abstract inputs for the given shape.  Training batches carry a
    leading worker axis [R, b, ...]; serving batches are [B, ...]."""
    sh = INPUT_SHAPES[shape_name]
    daxes = data_axes(mesh)
    if for_train:
        R = worker_count(mesh)
        b = max(1, sh.global_batch // R)
        batch = {"tokens": _sds((R, b, sh.seq_len + 1), jnp.int32, mesh,
                                P(tuple(daxes)))}
        if cfg.modality:
            batch["prefix_embeds"] = _sds(
                (R, b, cfg.n_frontend_tokens, cfg.d_model), cfg.adtype,
                mesh, P(tuple(daxes)))
        return batch
    B = sh.global_batch
    bspec = tuple(daxes) if B % max(worker_count(mesh), 1) == 0 else None
    batch = {"tokens": _sds((B, sh.seq_len), jnp.int32, mesh,
                            P(bspec))}
    if cfg.modality:
        batch["prefix_embeds"] = _sds(
            (B, cfg.n_frontend_tokens, cfg.d_model), cfg.adtype, mesh,
            P(bspec))
    return batch


def cache_shardings(cfg, cache_sds, mesh, batch_size: int):
    """Heuristic NamedShardings for decode caches: batch dim over the
    data axes (when divisible), largest model-divisible dim over 'model'."""
    daxes = data_axes(mesh)
    n_data = worker_count(mesh)
    n_model = mesh.shape["model"]

    def leaf(s):
        entries = [None] * len(s.shape)
        used_batch = used_model = False
        for ax, n in enumerate(s.shape):
            if not used_batch and n == batch_size and batch_size % n_data == 0:
                entries[ax] = tuple(daxes)
                used_batch = True
                break
        # biggest remaining axis divisible by model size
        best, best_ax = 0, None
        for ax, n in enumerate(s.shape):
            if entries[ax] is None and n % n_model == 0 and n > best and n >= n_model:
                best, best_ax = n, ax
        if best_ax is not None:
            entries[best_ax] = "model"
        return _sds(s.shape, s.dtype, mesh, P(*entries))

    return jax.tree_util.tree_map(leaf, cache_sds)


# ---------------------------------------------------------------------------
# lowering paths
# ---------------------------------------------------------------------------


def lower_train(cfg, mesh, *, zero1: bool = False, compressor_mode: str = "topk",
                k_frac: float = 0.01, seq_shard: bool = True,
                aggregate: str = "dense_psum"):
    """Lower + compile the Qsparse sync_step (the communication-bearing
    step) and the local step."""
    daxes = data_axes(mesh)
    model = get_model(cfg)
    policy = activation_policy(cfg, for_serving=False, data_axes=daxes,
                               seq_shard=seq_shard)

    def grad_fn(params, batch):
        def loss(p):
            l, _ = model.loss_fn(p, batch, cfg, policy)
            return l
        return jax.value_and_grad(loss)(params)

    specs = param_specs(cfg)
    init_fn, local_step, sync_step = make_dist_steps(
        grad_fn, momentum_sgd(0.9), ShardCompressor(compressor_mode, k_frac),
        constant(1e-3), mesh, daxes, specs, zero1=zero1,
        wire=aggregate,
    )
    params_sds, _ = abstract_params(cfg, mesh, model)
    state_sds = jax.eval_shape(init_fn, params_sds)
    # attach shardings to the state tree
    is_spec = lambda z: isinstance(z, P) or z is None

    def master_shard(s, spec):
        from repro.core.distributed import _zero1_axis
        spec = sanitize_spec(spec, s.shape, mesh)
        if zero1:
            R = worker_count(mesh)
            ax = _zero1_axis(s.shape, spec, R)
            if ax is not None:
                entries = list(spec) + [None] * (len(s.shape) - len(tuple(spec)))
                entries[ax] = tuple(daxes)
                spec = P(*entries)
        return _sds(s.shape, s.dtype, mesh, spec)

    def worker_shard(s, spec):
        entries = tuple(sanitize_spec(spec, s.shape[1:], mesh))
        return _sds(s.shape, s.dtype, mesh, P(tuple(daxes), *entries))

    def tmap(fn, tree, specs_tree):
        flat_s, treedef = jax.tree_util.tree_flatten(tree)
        flat_spec = jax.tree_util.tree_leaves(specs_tree, is_leaf=is_spec)
        if len(flat_spec) != len(flat_s):
            # inner-opt state may nest params-like trees (e.g. momentum "mu")
            reps = len(flat_s) // len(flat_spec)
            flat_spec = flat_spec * reps
        return jax.tree_util.tree_unflatten(
            treedef, [fn(s, sp) for s, sp in zip(flat_s, flat_spec)]
        )

    from repro.core.distributed import DistQsparseState
    state_sharded = DistQsparseState(
        master=tmap(master_shard, state_sds.master, specs),
        local=tmap(worker_shard, state_sds.local, specs),
        memory=tmap(worker_shard, state_sds.memory, specs),
        inner=tmap(worker_shard, state_sds.inner, specs),
        step=_sds((), jnp.int32, mesh, P()),
        bits=_sds((), jnp.float32, mesh, P()),
        rounds=_sds((), jnp.int32, mesh, P()),
    )
    batch_sds = input_specs(cfg, _CUR_SHAPE[0], mesh, for_train=True)
    key_sds = _sds((2,), jnp.uint32, mesh, P())
    results = {}
    # traced without a jax.set_mesh context, as the launcher runs it
    # (kernels/dispatch.py _split_rows): the engine's shard_maps and the
    # argument shardings name the mesh
    for name, fn in (("sync_step", sync_step), ("local_step", local_step)):
        # donate the state: steady-state training aliases the Qsparse
        # state buffers in place (alias_bytes in memory_analysis)
        lowered = jax.jit(fn, donate_argnums=(0,)).lower(
            state_sharded, batch_sds, key_sds)
        results[name] = lowered
    return results


def lower_serve(cfg, mesh, shape_name: str):
    """Lower + compile prefill (prefill_32k) or one decode step
    (decode_32k / long_500k)."""
    sh = INPUT_SHAPES[shape_name]
    daxes = data_axes(mesh)
    model = get_model(cfg)
    policy = activation_policy(cfg, for_serving=True, data_axes=daxes)
    params_sds, _ = abstract_params(cfg, mesh, model)
    results = {}
    if sh.kind == "prefill":
        batch_sds = input_specs(cfg, shape_name, mesh, for_train=False)

        def prefill_fn(params, batch):
            return model.prefill(params, batch, cfg, policy,
                                 max_len=sh.seq_len)

        with jax.set_mesh(mesh):
            results["prefill"] = jax.jit(prefill_fn).lower(params_sds, batch_sds)
        return results
    # decode: one new token against a seq_len cache
    B = sh.global_batch
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(cfg, B, sh.seq_len))
    cache_sharded = cache_shardings(cfg, cache_sds, mesh, B)
    bspec = tuple(daxes) if B % worker_count(mesh) == 0 else None
    token_sds = _sds((B,), jnp.int32, mesh, P(bspec))

    def decode_fn(params, cache, token):
        return model.decode_step(params, cache, token, sh.seq_len - 1, cfg,
                                 policy)

    with jax.set_mesh(mesh):
        results["decode"] = jax.jit(decode_fn).lower(
            params_sds, cache_sharded, token_sds)
    return results


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_CUR_SHAPE = ["train_4k"]


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            zero1: bool = False, compressor: str = "topk",
            seq_shard: bool = True, tag: str = "",
            smoke: bool = False, mesh=None, shape_override=None,
            aggregate: str = "dense_psum", cfg_overrides=None) -> dict:
    ok, reason = shape_supported(arch, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "zero1": zero1, "compressor": compressor, "seq_shard": seq_shard,
        "aggregate": aggregate, "tag": tag,
        "status": "skipped", "reason": reason,
    }
    if not ok:
        return record
    _CUR_SHAPE[0] = shape_name
    sh = shape_override or INPUT_SHAPES[shape_name]
    if shape_override is not None:
        INPUT_SHAPES[shape_name] = shape_override
    kw = {}
    if arch == "zamba2-7b" and shape_name == "long_500k" and not smoke:
        kw["long_context"] = True
    cfg = get_config(arch, smoke=smoke, **kw)
    if smoke and arch == "zamba2-7b" and shape_name == "long_500k":
        cfg = __import__("dataclasses").replace(cfg, swa_pattern=(64,))
    if cfg_overrides:
        cfg = __import__("dataclasses").replace(cfg, **cfg_overrides)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        if sh.kind == "train":
            lowered = lower_train(cfg, mesh, zero1=zero1,
                                  compressor_mode=compressor,
                                  seq_shard=seq_shard, aggregate=aggregate)
        else:
            lowered = lower_serve(cfg, mesh, shape_name)
        record["lower_s"] = round(time.time() - t0, 1)
        record["steps"] = {}
        for name, low in lowered.items():
            t1 = time.time()
            compiled = low.compile()
            mem = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            coll = collective_bytes(compiled.as_text())
            record["steps"][name] = {
                "compile_s": round(time.time() - t1, 1),
                "memory": {
                    "argument_bytes": int(mem.argument_size_in_bytes),
                    "output_bytes": int(mem.output_size_in_bytes),
                    "temp_bytes": int(mem.temp_size_in_bytes),
                    "alias_bytes": int(mem.alias_size_in_bytes),
                    "code_bytes": int(mem.generated_code_size_in_bytes),
                },
                "flops": float(ca.get("flops", -1)),
                "bytes_accessed": float(ca.get("bytes accessed", -1)),
                "collectives": coll,
            }
        record["status"] = "ok"
        record["params"] = cfg.param_count()
        record["active_params"] = cfg.active_param_count()
        record["n_devices"] = int(np.prod(list(mesh.shape.values())))
        record["model_axis"] = mesh.shape["model"]
        record["n_workers"] = worker_count(mesh)
        record["seq_len"] = sh.seq_len
        record["global_batch"] = sh.global_batch
        record["kind"] = sh.kind
    except Exception as e:  # noqa: BLE001 - report every failure mode
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    return record


def save_record(record: dict, tag: str = "") -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = (f"{ART_DIR}/{record['arch']}__{record['shape']}"
          f"__{record['mesh']}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(record, f, indent=2)
    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--compressor", default="topk",
                    choices=["topk", "signtopk", "none"])
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--aggregate", default="dense_psum",
                    choices=["dense_psum", "sparse_allgather"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs through the same lowering path")
    args = ap.parse_args()

    combos = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    failures = 0
    for a, s, mp in combos:
        rec = run_one(a, s, multi_pod=mp, zero1=args.zero1,
                      compressor=args.compressor,
                      seq_shard=not args.no_seq_shard, tag=args.tag,
                      smoke=args.smoke, aggregate=args.aggregate)
        fn = save_record(rec, tag=args.tag)
        status = rec["status"]
        extra = ""
        if status == "ok":
            st = next(iter(rec["steps"].values()))
            extra = (f"flops={st['flops']:.3g} "
                     f"temp={st['memory']['temp_bytes']/2**30:.2f}GiB "
                     f"coll={st['collectives']['total']/2**20:.1f}MiB")
        elif status == "error":
            failures += 1
            extra = rec["error"][:160]
        print(f"[{status:7s}] {a} x {s} x "
              f"{'2x16x16' if mp else '16x16'}  {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run failures")


if __name__ == "__main__":
    main()
