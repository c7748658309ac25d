"""Production training launcher.

Drives the distributed Qsparse-local-SGD engine (core/distributed.py)
for any assigned architecture on a jax mesh.  On TPU hardware this is
the per-host entry point; on a CPU it runs with forced host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --mesh 4x2 --steps 20 --H 4

``--layers N`` keeps an architecture's published widths and cuts its
depth to N layers (one chip's share; ``chip_smoke.py`` runs yi-6b so).
``main(argv)`` also runs in-process and returns the run's summary.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_policy_preset
from repro.core import policy as pol
from repro.core.distributed import (ShardCompressor, make_dist_round,
                                    make_dist_steps)
from repro.core.engine import stack_block
from repro.data import LMTokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import data_axes, parse_mesh, worker_count
from repro.models import get_model
from repro.optim import momentum_sgd, warmup_piecewise
from repro.sharding.specs import activation_policy, param_specs, sanitize_spec
from repro.train import checkpoint


def resolve_policy_arg(args) -> pol.ChannelSpec:
    """One ChannelSpec from the CLI surface (DESIGN.md §6).

    ``--policy`` takes an inline DSL string, ``@file.json`` (a
    ``to_dict()`` serialization) or ``preset:<name>`` /``preset:arch``
    (configs/policies.py).  The legacy ``--compressor``/``--downlink``
    flags map onto the equivalent catch-all policy behind a one-time
    deprecation warning; every name goes through the operator registry,
    so an unknown compressor or downlink fails loudly instead of
    silently meaning identity.
    """
    legacy = (args.compressor is not None or args.downlink is not None
              or args.downlink_k_frac is not None)
    if args.policy is not None:
        if legacy:
            raise SystemExit(
                "--policy conflicts with the deprecated --compressor/"
                "--downlink/--downlink-k-frac flags; put both directions "
                "in the policy ('uplink >> downlink')")
        if args.policy.startswith("preset:"):
            spec = get_policy_preset(args.policy[len("preset:"):],
                                     arch=args.arch)
        else:
            spec = pol.load(args.policy)
        return pol.as_channel_spec(spec)
    if legacy:
        pol.warn_once(
            "launch-legacy-flags",
            "--compressor/--downlink/--downlink-k-frac are deprecated; "
            "use --policy (e.g. --policy 'topk:k=0.01 >> topk:k=0.05')",
            stacklevel=2)
    up_name = args.compressor or "topk"
    up = (pol.PolicySpec.catch_all("identity") if up_name == "none"
          else pol.PolicySpec.catch_all(
              pol.OpSpec(up_name, (("k", args.k_frac),))
              if pol.OpSpec.parse(up_name).takes("k")
              else pol.OpSpec.parse(up_name)))
    down = None
    if args.downlink is not None and args.downlink != "identity":
        dk = (args.downlink_k_frac if args.downlink_k_frac is not None
              else args.k_frac)
        dspec = (pol.OpSpec(args.downlink, (("k", dk),))
                 if pol.OpSpec.parse(args.downlink).takes("k")
                 else pol.OpSpec.parse(args.downlink))
        down = pol.PolicySpec.catch_all(dspec)
    return pol.ChannelSpec(up, down)


def resolve_config(arch: str, smoke: bool, layers):
    """The run's ModelConfig: the smoke config, or the published one —
    with its depth cut to ``layers`` when given (widths untouched)."""
    if layers is not None and smoke:
        raise SystemExit("--layers cuts the published config's depth; "
                         "it does not apply to --smoke")
    cfg = get_config(arch, smoke=smoke)
    if layers is not None:
        if layers < 1:
            raise SystemExit(f"--layers must be >= 1, got {layers}")
        cfg = dataclasses.replace(cfg, n_layers=layers)
    return cfg


def main(argv=None, *, return_master: bool = False) -> dict:
    """Train; returns the per-step losses and uplink wire bits, the
    kernel launch counts and where the state lives (see the end of this
    function).  ``return_master`` adds host copies of the initial and
    final master parameters (``master0``, ``master``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=None,
                    help="published widths at this depth (not with "
                         "--smoke)")
    ap.add_argument("--mesh", default="4x2",
                    help="DxM or PxDxM device mesh, e.g. 16x16 or 2x16x16")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2, help="per-worker batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--H", type=int, default=4)
    ap.add_argument("--k-frac", type=float, default=0.01)
    ap.add_argument("--policy", default=None,
                    help="compression policy (DESIGN.md §6): inline DSL "
                         "('norm->identity;.*->topk:k=0.01', uplink '>>' "
                         "downlink), @file.json, or preset:<name>|"
                         "preset:arch (configs/policies.py)")
    ap.add_argument("--compressor", default=None,
                    choices=["topk", "signtopk", "none"],
                    help="DEPRECATED: use --policy")
    ap.add_argument("--dispatch", default="auto",
                    choices=["auto", "kernel", "reference"],
                    help="compression kernel routing (kernels/dispatch.py): "
                         "auto = fused Pallas Top_k on TPU, reference "
                         "elsewhere")
    ap.add_argument("--aggregate", default="mean_R",
                    choices=["mean_R", "mean_S", "support_weighted",
                             "dense_psum", "sparse_allgather"],
                    help="master division rule over the syncing subset "
                         "(DESIGN.md §8): mean_R (the paper's Σ/R), "
                         "mean_S (Σ/|S|), or support_weighted (per-"
                         "coordinate survivor count).  The legacy wire "
                         "values dense_psum|sparse_allgather are shimmed "
                         "onto --wire with a one-time warning")
    ap.add_argument("--wire", default="dense_psum",
                    choices=["dense_psum", "sparse_allgather"],
                    help="sync transport: dense psum, or compact "
                         "(idx, val) allgather (the sparse wire format)")
    ap.add_argument("--scenario", default=None,
                    help="fleet scenario (core/scenarios.py, DESIGN.md "
                         "§8): 'preset:<name>' (e.g. preset:flaky_fleet) "
                         "or 'k=v,...' (participation=0.8,"
                         "straggler_frac=0.1,seed=3) — generates the "
                         "[T, R] per-worker sync mask; --H is the base "
                         "sync period")
    ap.add_argument("--runtime", default="round",
                    choices=["round", "step"],
                    help="execution runtime (DESIGN.md §7): 'round' "
                         "compiles each sync round (H local steps + "
                         "sync) into one scanned, donated program; "
                         "'step' keeps per-step dispatch.  Identical "
                         "trajectories")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped round driver (DESIGN.md §10): "
                         "dispatch windows of consecutive equal-length "
                         "rounds as one scanned multi-round program, "
                         "pipelining each round's sync collective "
                         "against the next round's local compute.  "
                         "Bit-for-bit trajectories; the wire-bits log "
                         "coarsens to window granularity.  Requires "
                         "--runtime round; unsupported with --faults")
    ap.add_argument("--overlap-window", type=int, default=8,
                    help="max rounds per overlapped window "
                         "(power-of-2 chunks)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune the run's compression-kernel launch "
                         "signatures (kernels/autotune.py) before "
                         "training and persist the winning block "
                         "geometry to the per-device tuning table "
                         "(artifacts/tuning/<device>.json); already-"
                         "tuned signatures are reused")
    ap.add_argument("--retune", action="store_true",
                    help="with --tune: re-measure signatures already "
                         "in the tuning table")
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec (core/scenarios.py "
                         "FaultSpec, DESIGN.md §9): 'preset:<name>' "
                         "(e.g. preset:chaos) or 'k=v,...' "
                         "(max_delay=3,drop=0.1,crash_rate=0.02,seed=5) "
                         "— executed staleness: payloads computed at t "
                         "land on the master at t+τ out of per-worker "
                         "in-flight queues, with crash/recover and "
                         "payload drop")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="override the fault spec's PRNG seed (its own "
                         "stream — never perturbs batch construction)")
    ap.add_argument("--staleness-weight", default="uniform",
                    choices=["uniform", "damped"],
                    help="weighting of delayed payloads at apply time: "
                         "uniform (as computed) or damped (1/(1+τ))")
    ap.add_argument("--downlink", default=None,
                    help="DEPRECATED: use --policy 'up >> down'.  "
                         "Registry operator name for the server→worker "
                         "channel (identity = exact dense broadcast)")
    ap.add_argument("--downlink-k-frac", type=float, default=None,
                    help="DEPRECATED: survivor fraction of the downlink "
                         "channel (default: --k-frac)")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)

    cfg = resolve_config(args.arch, args.smoke, args.layers)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    mesh = parse_mesh(args.mesh)
    daxes = data_axes(mesh)
    R = worker_count(mesh)
    print(f"arch={args.arch} layers={cfg.n_layers} d={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} ff={cfg.d_ff} "
          f"V={cfg.vocab} {cfg.param_dtype} mesh={dict(mesh.shape)}",
          flush=True)
    model = get_model(cfg)
    policy = activation_policy(cfg, for_serving=False, data_axes=daxes)
    specs = param_specs(cfg)

    def grad_fn(params, batch):
        def loss(p):
            l, _ = model.loss_fn(p, batch, cfg, policy)
            return l
        return jax.value_and_grad(loss)(params)

    # params first: the policy resolves per leaf against their paths
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    master0 = jax.device_get(params) if return_master else None
    channel_spec = resolve_policy_arg(args)
    print("policy:", channel_spec.to_string(), flush=True)
    if args.tune:
        from repro.kernels import autotune
        from repro.kernels.dispatch import DispatchConfig
        up_tree, down_tree = channel_spec.resolve(params)
        fresh = autotune.tune_for_run(
            up_tree, params, DispatchConfig(mode=args.dispatch),
            downlink=down_tree, retune=args.retune)
        print(f"tune: {len(fresh)} measured, "
              f"{autotune.tune.last_cached} cached -> "
              f"{autotune.table_path()}", flush=True)
    uplink = ShardCompressor.from_spec(
        channel_spec.uplink, params, dispatch=args.dispatch)
    downlink = None
    if channel_spec.downlink is not None:
        downlink = ShardCompressor.from_spec(
            channel_spec.downlink, params, dispatch=args.dispatch)
    engine_args = (
        grad_fn, momentum_sgd(0.9),
        uplink if uplink is not None
        else ShardCompressor("none", dispatch=args.dispatch),
        warmup_piecewise(args.lr, 5, [int(args.steps * 0.8)]),
        mesh, daxes, specs,
    )
    scenario_mask = None
    if args.scenario is not None:
        from repro.core import scenarios as scn
        scenario = scn.parse(args.scenario)
        scenario_mask = scenario.mask(args.steps, R, H=args.H)
        scn.warn_if_biased(scenario_mask, args.aggregate)
        print(f"scenario: {scenario.to_string() or 'lossless'} "
              f"(participation {scn.participation_of(scenario_mask):.2f}, "
              f"{int(scenario_mask.any(axis=1).sum())} sync steps)",
              flush=True)
    fault_spec = fault_rows = fault_events = None
    if args.faults is not None:
        import dataclasses as _dc

        from repro.core import engine as engine_mod
        from repro.core import scenarios as scn
        if args.zero1:
            raise SystemExit("--faults does not support --zero1 (the "
                             "recover phase needs the full master)")
        if downlink is not None:
            raise SystemExit("--faults does not support a compressed "
                             "downlink on the mesh engine; drop the "
                             "'>> down' half of the policy")
        fault_spec = scn.parse_faults(args.faults)
        if args.fault_seed is not None:
            fault_spec = _dc.replace(fault_spec, seed=int(args.fault_seed))
        base_mask = (scenario_mask if scenario_mask is not None
                     else np.array([(t + 1) % args.H == 0
                                    or t == args.steps - 1
                                    for t in range(args.steps)]))
        fault_tables = fault_spec.tables(args.steps, R)
        fault_rows = engine_mod.fault_rows(base_mask, fault_tables, R)
        _, fault_arrivals, fault_events = scn.fault_replay(
            fault_rows.sync, fault_tables)
        print(f"faults: {fault_spec.to_string() or 'none'} "
              f"(queue depth {fault_spec.depth}, "
              f"{int(fault_arrivals.sum())} arrivals, "
              f"{int((~fault_tables.alive).sum())} crashed worker-steps, "
              f"weighting {args.staleness_weight})", flush=True)
    engine_kw = dict(zero1=args.zero1, aggregate=args.aggregate,
                     downlink=downlink, wire=args.wire,
                     partial=scenario_mask is not None)
    if args.overlap:
        if args.runtime != "round":
            raise SystemExit("--overlap requires --runtime round")
        if args.faults is not None:
            raise SystemExit(
                "--overlap is unsupported with --faults: arrival "
                "events segment rounds dynamically")
    if fault_spec is not None:
        from repro.core.distributed import (make_dist_fault_round,
                                            make_dist_fault_steps)
        fault_kw = dict(queue_depth=fault_spec.depth,
                        aggregate=args.aggregate, wire=args.wire,
                        staleness_weight=args.staleness_weight)
        if args.runtime == "round":
            init_fn, round_fn = make_dist_fault_round(
                *engine_args, **fault_kw)
            print("runtime: fault round", flush=True)
        else:
            init_fn, local_step, sync_step = make_dist_fault_steps(
                *engine_args, **fault_kw)
    elif args.runtime == "round" and args.overlap:
        from repro.core.distributed import make_dist_multiround
        init_fn, multi_fn = make_dist_multiround(*engine_args, **engine_kw)
        print(f"runtime: round overlap, window {args.overlap_window}",
              flush=True)
    elif args.runtime == "round":
        init_fn, round_fn = make_dist_round(*engine_args, **engine_kw)
        print("runtime: round", flush=True)
    else:
        init_fn, local_step, sync_step = make_dist_steps(*engine_args,
                                                         **engine_kw)
    from jax.sharding import NamedSharding, PartitionSpec as P
    put_specs = jax.tree_util.tree_map(
        lambda leaf, sp: NamedSharding(
            mesh, sanitize_spec(sp, leaf.shape, mesh)),
        params, specs,
        is_leaf=lambda z: hasattr(z, "shape") and not isinstance(z, dict),
    )
    from repro.kernels.dispatch import LAUNCHES, reset_launches

    def make_batch(batch, sub):
        b = {"tokens": jnp.asarray(batch["tokens"])}
        if cfg.modality:
            b["prefix_embeds"] = 0.02 * jax.random.normal(
                sub, (R, args.batch, cfg.n_frontend_tokens, cfg.d_model))
        return b

    def launch_note_once():
        return " ".join(f"{k}={v}" for k, v in LAUNCHES.items() if v) or "none"

    history = []  # (step, loss, uplink bits) as logged

    def log_step(t, kind, loss, up, down, note=""):
        history.append((t + 1, loss, up))
        print(f"step {t + 1:4d} [{kind}] loss {loss:.4f} "
              f"bits up {up:.3g} down {down:.3g}{note}", flush=True)

    # no jax.set_mesh context: the engine's shard_maps name their mesh,
    # and the Pallas compressors split rows over the 'model' axis in a
    # nested shard_map that a concrete context mesh would break on TPU
    # (kernels/dispatch.py _split_rows)
    params = jax.device_put(params, put_specs)
    state = init_fn(params)
    stream = LMTokenStream(vocab=cfg.vocab, R=R, order=64, seed=0)
    # committed like the key the round program hands back, so the
    # second round does not compile the program again
    key = jax.device_put(jax.random.PRNGKey(1), NamedSharding(mesh, P()))
    # kernel launches are counted at trace time (launch_stats.py):
    # snapshot after the first sync step traces — with megabuffer
    # packing this shows one launch per operator family per
    # direction per sync round, regardless of leaf count
    reset_launches()
    launch_note = None

    def is_sync_step(t):
        """Scenario runs sync where any worker's mask row fires; the
        fixed schedule keeps the historical every-H + final step.
        Fault runs close at *event* steps — any scheduled sync row
        or any queued-payload arrival (scenarios.fault_replay)."""
        if fault_events is not None:
            return bool(fault_events[t])
        if scenario_mask is not None:
            return bool(scenario_mask[t].any())
        return (t + 1) % args.H == 0 or t == args.steps - 1

    if args.runtime == "round" and args.overlap:
        # overlapped round runtime (DESIGN.md §10): windows of
        # consecutive equal-length rounds run as ONE scanned
        # multi-round program — the sync collective of round w
        # pipelines against round w+1's local compute.  Same key
        # threading as the per-round loop below, so trajectories
        # match; wire-bit logging coarsens to window granularity
        # (interior tail steps show the pre-window totals).
        from repro.core import rounds as rnd_mod
        plans, s0 = [], 0
        for t in range(args.steps):
            if is_sync_step(t) or t == args.steps - 1:
                tail = (scenario_mask[t] if scenario_mask is not None
                        else np.asarray(is_sync_step(t)))
                plans.append(rnd_mod.RoundPlan(
                    s0, t - s0 + 1, np.asarray(tail)))
                s0 = t + 1
        windows = rnd_mod.window_rounds(
            plans, max_window=args.overlap_window)
        batch_iter = stream.batches(args.batch, args.seq, args.steps,
                                    seed=1)
        mirror = key
        for win in windows:
            W, L = len(win), win[0].length
            pending = []
            for _ in range(W * L):
                mirror, sub = jax.random.split(mirror)
                pending.append(make_batch(next(batch_iter), sub))
            blocks = jax.tree_util.tree_map(
                lambda x: x.reshape((W, L) + x.shape[1:]),
                stack_block(pending))
            prev_up = float(state.bits)
            prev_down = float(state.bits_down)
            if scenario_mask is not None:
                masks_arr = jnp.asarray(
                    np.stack([np.asarray(p.mask) for p in win]))
                state, losses, key = multi_fn(state, blocks,
                                              masks_arr, key)
            else:
                state, losses, key = multi_fn(state, blocks, key)
            mirror = key
            if launch_note is None:
                launch_note = launch_note_once()
            losses = np.asarray(losses)
            for wi, plan in enumerate(win):
                for i in range(L):
                    tail = i == L - 1
                    final = tail and wi == W - 1
                    last_loss = float(losses[wi, i])
                    log_step(
                        plan.start + i,
                        "sync " if tail and is_sync_step(plan.stop - 1)
                        else "local",
                        last_loss,
                        float(state.bits) if final else prev_up,
                        float(state.bits_down) if final else prev_down,
                        f" launches/round [{launch_note}]"
                        if final else "")
    elif args.runtime == "round":
        # round runtime (DESIGN.md §7): accumulate steps until the
        # schedule's next sync, run the block as one program.  The
        # round program splits the PRNG key in-program with the
        # same per-step sequence this host mirror uses for batch
        # construction, so trajectories match --runtime step.
        pending, block_start, mirror = [], 0, key
        for t, batch in enumerate(
                stream.batches(args.batch, args.seq, args.steps,
                               seed=1)):
            mirror, sub = jax.random.split(mirror)
            pending.append(make_batch(batch, sub))
            # scenario runs close rounds at any-worker-sync steps
            # (an all-False final flush is legal: the masked tail
            # sync is exactly a local step on every worker)
            if not (is_sync_step(t) or t == args.steps - 1):
                continue
            block = stack_block(pending)
            prev_up, prev_down = float(state.bits), float(state.bits_down)
            if fault_rows is not None:
                from repro.core.engine import index_rows
                rblock = index_rows(fault_rows,
                                    slice(block_start, t + 1))
                state, losses, key = round_fn(state, block, rblock, key)
            elif scenario_mask is not None:
                state, losses, key = round_fn(
                    state, block, jnp.asarray(scenario_mask[t]), key)
            else:
                state, losses, key = round_fn(state, block, key)
            mirror = key
            if launch_note is None:
                launch_note = launch_note_once()
            losses = np.asarray(losses)
            for i in range(len(pending)):
                tail = i == len(pending) - 1
                last_loss = float(losses[i])
                log_step(
                    block_start + i,
                    "sync " if tail and is_sync_step(t) else "local",
                    last_loss,
                    float(state.bits) if tail else prev_up,
                    float(state.bits_down) if tail else prev_down,
                    f" launches/round [{launch_note}]" if tail else "")
            pending, block_start = [], t + 1
    else:
        ls, ss = jax.jit(local_step), jax.jit(sync_step)
        for t, batch in enumerate(
                stream.batches(args.batch, args.seq, args.steps,
                               seed=1)):
            key, sub = jax.random.split(key)
            b = make_batch(batch, sub)
            if is_sync_step(t):
                if fault_rows is not None:
                    from repro.core.engine import index_rows
                    state, loss = ss(state, b, index_rows(fault_rows, t),
                                     sub)
                elif scenario_mask is not None:
                    state, loss = ss(state, b, sub,
                                     jnp.asarray(scenario_mask[t]))
                else:
                    state, loss = ss(state, b, sub)
                kind = "sync "
                if launch_note is None:
                    launch_note = launch_note_once()
                note = f" launches/round [{launch_note}]"
            else:
                if fault_rows is not None:
                    from repro.core.engine import index_rows
                    state, loss = ls(state, b, index_rows(fault_rows, t),
                                     sub)
                else:
                    state, loss = ls(state, b, sub)
                kind = "local"
                note = ""
            last_loss = float(loss)
            log_step(t, kind, last_loss, float(state.bits),
                     float(state.bits_down), note)
    total = float(state.bits) + float(state.bits_down)
    print(f"\n{args.steps} steps; "
          f"R={R} workers, {int(state.rounds)} sync rounds, "
          f"{float(state.bits):.3g} uplink + {float(state.bits_down):.3g} "
          f"downlink = {total:.3g} wire bits")
    assert np.isfinite(last_loss)
    if args.ckpt:
        # persist the policy spec so a resume reproduces the exact
        # per-leaf operators (and hence the bits trajectories)
        checkpoint.save(args.ckpt, state.master, step=args.steps,
                        policy=channel_spec.to_dict())
        print("checkpoint saved to", args.ckpt)
    # where the state lives: every device the state touches, and the
    # devices holding the worker axis of the largest per-worker leaf
    big = max(jax.tree_util.tree_leaves(state.local), key=lambda x: x.size)
    summary = {
        "steps": [h[0] for h in history],
        "losses": [h[1] for h in history],
        "bits_up": [h[2] for h in history],
        "launches": dict(LAUNCHES),
        "rounds": int(state.rounds),
        "workers": R,
        "layers": cfg.n_layers,
        "state_devices": sorted({
            d.id for leaf in jax.tree_util.tree_leaves(state)
            for d in leaf.sharding.device_set}),
        "worker_shard_devices": sorted(
            sh.device.id for sh in big.addressable_shards),
        "worker_shard_shape": tuple(big.sharding.shard_shape(big.shape)),
    }
    if return_master:
        summary["master0"] = master0
        summary["master"] = jax.device_get(state.master)
    return summary


if __name__ == "__main__":
    main()
