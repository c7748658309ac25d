"""Serving launcher: compressed checkpoints through the
continuous-batching engine (DESIGN.md §11).

Loads real trained checkpoints — a compact serving checkpoint
(``checkpoint.save_compact``) is consumed directly in compressed form;
a dense training checkpoint is restored and, under ``--compressed``,
compressed once at load time with the policy spec persisted in its own
manifest (``--policy`` overrides).  The request runtime is
``serve.engine.ServeEngine``: admission queue, prefill/decode
interleave, slot reuse, per-request metrics.

  # train then serve the smoke model compressed:
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 4 --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --checkpoint /tmp/ck --compressed --scheduler continuous

``--layers N`` serves the published widths at depth N (the depth the
checkpoint was trained at).  ``main(argv)`` also runs in-process and
returns the run's summary.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import ARCHS
from repro.configs.policies import get_policy_preset
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import resolve_config
from repro.models import get_model
from repro.serve import compressed as sc
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as ckpt


def resolve_policy(arg: str | None, checkpoint_path: str | None,
                   arch: str | None):
    """Policy used for --compressed: explicit --policy (DSL string,
    @file.json, or preset:<name>|preset:arch) wins; otherwise the spec
    persisted in the checkpoint manifest; otherwise the arch preset."""
    from repro.core import policy as pol
    if arg:
        if arg.startswith("preset:"):
            return get_policy_preset(arg[len("preset:"):], arch)
        return pol.load(arg)
    if checkpoint_path:
        spec = ckpt.load_policy(checkpoint_path)
        if spec is not None:
            return spec
    return get_policy_preset("arch", arch)


def load_params(args, cfg, model):
    """(params, source) — compact checkpoints stay compressed; dense
    checkpoints restore into the model structure and optionally
    compress once at load."""
    if args.checkpoint and ckpt.is_compact(args.checkpoint):
        return ckpt.load_compact(args.checkpoint), "compact checkpoint"
    if args.checkpoint:
        like = model.init_params(jax.random.PRNGKey(0), cfg)
        params = ckpt.restore(args.checkpoint, like)
        src = "dense checkpoint"
    else:
        params = model.init_params(jax.random.PRNGKey(0), cfg)
        src = "random init (no --checkpoint)"
    if args.compressed:
        if cfg.family != "dense":
            raise SystemExit(
                f"--compressed serves the dense transformer family only "
                f"(arch {args.arch} is family={cfg.family!r})")
        policy = resolve_policy(args.policy, args.checkpoint, args.arch)
        params = sc.compress_tree(params, policy)
        src += " -> compressed at load"
    return params, src


def main(argv=None) -> dict:
    """Serve; returns the run's summary (requests finished, tokens,
    the densify count and the page pool's statistics)."""
    ap = argparse.ArgumentParser(
        description="serve a (compressed) checkpoint with continuous "
                    "batching")
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="published widths at this depth (not with "
                         "--smoke)")
    ap.add_argument("--checkpoint", default=None,
                    help="dense (train --ckpt) or compact "
                         "(save_compact) checkpoint directory")
    ap.add_argument("--compressed", action="store_true",
                    help="serve from compressed weights (policy-guided "
                         "one-shot compression for dense checkpoints)")
    ap.add_argument("--policy", default=None,
                    help="compression policy override: DSL string, "
                         "@file.json, preset:<name> or preset:arch "
                         "(default: the checkpoint's persisted spec)")
    ap.add_argument("--scheduler", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (continuous-batching width)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="synthetic prompt length cap (also the static "
                         "prefill pad)")
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8,
                    help="synthetic request count")
    ap.add_argument("--flash", action="store_true",
                    help="route decode attention through the Pallas "
                         "flash-decode kernel (paged: the paged kernel)")
    ap.add_argument("--paged", action="store_true",
                    help="shared KV page pool + block tables instead of "
                         "per-slot contiguous caches (DESIGN.md §12)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 pages + per-token-slot f32 scales "
                         "(--paged; 4x KV HBM at f32)")
    ap.add_argument("--kv-pool-pages", type=int, default=None,
                    help="total pool pages (default: max-batch * "
                         "ceil(max-len / page-size), the contiguous "
                         "layout's HBM equivalent)")
    ap.add_argument("--dispatch", choices=("auto", "kernel", "reference"),
                    default="auto",
                    help="compressed-GEMM dispatch mode (kernel uses "
                         "interpret off-TPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None,
                    help="write the metrics summary to this file")
    args = ap.parse_args(argv)

    cfg = resolve_config(args.arch, args.smoke, args.layers)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.flash:
        import dataclasses
        cfg = dataclasses.replace(cfg, use_pallas=True)
    if cfg.family != "dense":
        raise SystemExit(
            f"the serving engine drives the dense transformer family "
            f"(arch {args.arch} is family={cfg.family!r})")
    model = get_model(cfg)
    from repro.kernels.dispatch import DispatchConfig
    sc.set_dispatch(DispatchConfig(mode=args.dispatch))

    params, source = load_params(args, cfg, model)
    sc.reset_stats()
    sizes = sc.tree_bytes(params)
    print(f"arch={args.arch} layers={cfg.n_layers} source: {source}")
    print(f"resident params: {sizes['compressed'] / 1e6:.2f} MB "
          f"(dense equivalent {sizes['dense'] / 1e6:.2f} MB, "
          f"{sizes['leaves']} leaves)")

    if args.kv_quant and not args.paged:
        raise SystemExit("--kv-quant requires --paged")
    eng = ServeEngine(params, cfg, max_batch=args.max_batch,
                      max_len=args.max_len, prompt_pad=args.prompt_len,
                      scheduler=args.scheduler, paged=args.paged,
                      page_size=args.page_size, kv_quant=args.kv_quant,
                      kv_pool_pages=args.kv_pool_pages)
    rng = np.random.RandomState(args.seed)
    for _ in range(args.requests):
        plen = int(rng.randint(max(2, args.prompt_len // 2),
                               args.prompt_len + 1))
        eng.submit(rng.randint(0, cfg.vocab, plen).tolist(),
                   max_new_tokens=args.new_tokens)
    res = eng.run()

    mets = sorted(res["metrics"].values(), key=lambda m: m.rid)
    print(f"\nscheduler={args.scheduler} slots={args.max_batch} "
          f"requests={len(mets)} steps={res['steps']}")
    print(" rid  plen  new   wait_ms   ttft_ms    tok/s")
    for m in mets:
        print(f"{m.rid:4d} {m.prompt_len:5d} {m.new_tokens:4d} "
              f"{m.queue_wait_s * 1e3:9.1f} {m.ttft_s * 1e3:9.1f} "
              f"{m.tokens_per_s:8.1f}")
    print(f"\naggregate: {res['requests_per_s']:.2f} req/s, "
          f"{res['tokens_per_s']:.1f} tok/s, "
          f"wall {res['wall_s']:.2f}s, peak occupancy "
          f"{max(eng.occupancy) if eng.occupancy else 0}/{args.max_batch}")
    if args.paged:
        pool = res["pool"]
        print(f"page pool: {pool['peak_pages_used']}/{pool['n_pages']} "
              f"peak pages ({pool['page_size']} tok/page, "
              f"{'int8' if pool['kv_quant'] else 'fp'} layout), "
              f"{pool['pages_used']} in use at exit")
        print(f"  preemptions={pool['preemptions']} "
              f"admission_stalls={pool['admission_stalls']} "
              f"fragmentation={pool['fragmentation']:.4f}")
    print(f"serve stats: {sc.STATS}")
    if args.compressed and sc.STATS["densify"]:
        raise SystemExit("zero-densify violated: the serving path "
                         f"densified {sc.STATS['densify']} leaves")
    payload = {
        "requests_per_s": res["requests_per_s"],
        "tokens_per_s": res["tokens_per_s"],
        "steps": res["steps"],
        "densify": sc.STATS["densify"],
        "finished": len(mets),
        "new_tokens": [m.new_tokens for m in mets],
    }
    if args.paged:
        payload["pool"] = res["pool"]
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
    sample = res["outputs"].get(0, [])[:10]
    print("sample:", sample)
    return payload


if __name__ == "__main__":
    main()
