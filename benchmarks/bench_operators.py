"""Figure-1a/4a companion: per-operator compression quality, wire bits
per round and compression-op throughput on a ResNet-50-sized tensor —
plus the kernel-dispatch path (kernels/dispatch.py) vs the dense
references, the compact wire path (kernel (idx, val) emission vs the
scatter-free reference oracle), and the megabuffer packing of a full
sync round (kernel launches per round + rounds/sec, packed vs
leaf-by-leaf)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import BenchRow
from benchmarks import roofline
from repro.core import operators as ops
from repro.kernels import autotune
from repro.kernels import dispatch as dsp
from repro.kernels import topk_compress as _topk

D = 1_000_000   # ~ one large layer
D_GLOBAL = 1 << 18  # single-kernel-row budget for the global operators


def _launch_keys(op, data, *, compact=False):
    """The autotune ShapeKeys one benchmark entry dispatches."""
    return dsp.launch_plans(op, [data], dsp.DispatchConfig(mode="kernel"),
                            compact=compact)


def _model_bytes(keys):
    """Bytes-moved model (roofline.kernel_bytes_moved) summed over the
    launches of one benchmark entry."""
    total = 0.0
    for key in keys:
        kcap = (dsp.capacity(key.k, key.row_len)
                if key.kernel == "topk_compact" else None)
        total += roofline.kernel_bytes_moved(
            key.kernel, key.rows, key.row_len, key.k, kcap=kcap)
    return total


def _tuned_geometry(keys):
    """derived-string fragment naming the table-resolved block geometry
    of the entry's (first) launch, or the untuned one: Top_k's shape
    rule, the other kernels' default."""
    if not keys:
        return f"block_rows={dsp.DEFAULT_BLOCK_ROWS}"
    ent = autotune.lookup(*keys[0][:5])
    if ent is None:
        k0 = keys[0]
        br = (_topk.dense_block_rows(k0.rows, k0.row_len)
              if k0.kernel == "topk_compress" else dsp.DEFAULT_BLOCK_ROWS)
        return f"block_rows={br}"
    frag = f"block_rows={ent.block_rows}"
    if ent.chunk:
        frag += f";chunk={ent.chunk}"
    return frag


def _time(fn, *args, n=5):
    """Best-of-N wall time after one warmup (compile) call — the same
    policy as autotune._time_us: the min is robust to the scheduler
    noise that a mean of back-to-back calls folds in, which matters
    for the kernel-vs-reference row pairs the regression gate judges."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6


def run():
    x = jax.random.normal(jax.random.PRNGKey(0), (D,))
    rows = []
    table = [
        ("identity", ops.Identity()),
        ("topk_1pct", ops.TopK(k=0.01)),
        ("randk_1pct", ops.RandK(k=0.01)),
        ("qsgd_4bit", ops.QSGDQuantizer(s=15)),
        ("sign", ops.Sign()),
        ("qtopk_1pct_4bit", ops.QuantizedSparsifier(k=0.01, s=15)),
        ("qtopk_scaled", ops.QuantizedSparsifier(k=0.01, s=15, scaled=True)),
        ("signtopk_1pct", ops.SignSparsifier(k=0.01, m=1)),
        ("row_topk", ops.RowTopK(k=0.01, row_len=8192)),
    ]
    for name, op in table:
        fn = jax.jit(lambda k, v, o=op: o(k, v))
        (out, bits), us = _time(fn, jax.random.PRNGKey(1), x)
        rel_err = float(jnp.sum((x - out) ** 2) / jnp.sum(x ** 2))
        ratio = float(bits) / (32 * D)
        rows.append(BenchRow(
            f"op/{name}", us,
            f"rel_err={rel_err:.4f};wire_ratio={ratio:.5f};"
            f"gamma={op.gamma(D):.5f}",
            wire_bits=float(bits), path="reference"))

    # kernel-dispatch path vs reference on the dispatchable operators
    # (interpret mode off-TPU: a correctness/rel-err companion there,
    #  a speed comparison on real TPU backends)
    xg = x[:D_GLOBAL]
    dispatch_table = [
        ("topk_1pct", ops.TopK(k=0.01), xg),
        ("signtopk_1pct_m2", ops.SignSparsifier(k=0.01, m=2), xg),
        ("row_topk", ops.RowTopK(k=0.01, row_len=8192), x),
        ("row_signtopk", ops.RowSignTopK(k=0.01, row_len=8192), x),
        ("qsgd_4bit", ops.QSGDQuantizer(s=15), xg),
    ]
    # autotune the dispatch signatures first (DESIGN.md §10): the kernel
    # rows below resolve their block geometry through the tuning table,
    # exactly as a tuned (--tune) training run would.  The table is
    # persisted per device kind, so re-runs cache-hit and cost nothing.
    tune_keys = []
    for _n, op, data in dispatch_table:
        for key in _launch_keys(op, data):
            if key not in tune_keys:
                tune_keys.append(key)
    autotune.tune(tune_keys)

    for name, op, data in dispatch_table:
        d = int(data.size)
        assert dsp.would_dispatch(op, data.shape,
                                  cfg=dsp.DispatchConfig(mode="kernel")), name
        keys = _launch_keys(op, data)
        mbytes = _model_bytes(keys)
        for mode in ("kernel", "reference"):
            cfg = dsp.DispatchConfig(mode=mode)
            fn = jax.jit(lambda k, v, o=op, c=cfg: dsp.compress_leaf(
                o, k, v, c)[:2])
            (out, bits), us = _time(fn, jax.random.PRNGKey(1), data)
            rel_err = float(jnp.sum((data - out) ** 2) / jnp.sum(data ** 2))
            derived = (f"rel_err={rel_err:.4f};"
                       f"wire_ratio={float(bits) / (32 * d):.5f}")
            if mode == "kernel":
                # %-of-HBM-bound: roofline floor (bytes model / HBM_BW)
                # over measured time — near 100 means memory-bound
                derived += (f";pct_hbm={roofline.pct_hbm_bound(us, mbytes):.1f}"
                            f";{_tuned_geometry(keys)}")
            rows.append(BenchRow(
                f"dispatch/{name}/{mode}", us, derived,
                wire_bits=float(bits), path=mode))

    # compact wire path: the kernel's direct (idx, val) emission vs the
    # scatter-free reference oracle (the sparse_allgather hot loop).
    # Global rows sized so kcap fits the kernel's capacity bound.
    xc = x[: 1 << 17]
    compact_table = [
        ("topk_1pct", ops.TopK(k=0.01), xc),
        ("signtopk_1pct_m2", ops.SignSparsifier(k=0.01, m=2), xc),
        ("row_topk", ops.RowTopK(k=0.01, row_len=8192), x),
        ("row_signtopk", ops.RowSignTopK(k=0.01, row_len=8192), x),
    ]
    for name, op, data in compact_table:
        d = int(data.size)
        ckeys = _launch_keys(op, data, compact=True)
        cbytes = _model_bytes(ckeys)
        for mode in ("kernel", "reference"):
            cfg = dsp.DispatchConfig(mode=mode)
            fn = jax.jit(lambda k, v, o=op, c=cfg: dsp.compact_compress(
                o, k, v, c)[0])
            used = dsp.would_compact(op, data.shape, cfg=cfg)
            assert used == (mode == "kernel"), (name, mode)
            leaf, us = _time(fn, jax.random.PRNGKey(1), data)
            bits = float(leaf.bits)
            derived = f"wire_ratio={bits / (32 * d):.5f};kcap={leaf.kcap}"
            if used:
                derived += f";pct_hbm={roofline.pct_hbm_bound(us, cbytes):.1f}"
            rows.append(BenchRow(
                f"compact/{name}/{mode}", us, derived,
                wire_bits=bits,
                path="kernel" if used else "reference"))

    rows.extend(_bench_packing())
    rows.extend(_bench_channel_round())
    rows.extend(_bench_hetero_policy())
    rows.extend(_bench_runtime())
    return rows


def _bench_runtime():
    """Round-program runtime vs the per-step host loop (DESIGN.md §7):
    the same T-step fixed-H schedule driven per step (one jitted,
    donated dispatch + loss fetch per step) vs as compiled round
    programs (lax.scan over the local phase, sync at the tail, one
    fetch per round).  us_per_call is per *step*; with H=8 the local
    phase dominates, so the row pair gates the host-overhead win the
    runtime exists for.  Identical wire bits — a ledger-parity pin."""
    from repro.core import engine, schedule
    from repro.optim import constant, sgd

    R_, D_, H_, T_ = 4, 4096, 8, 48
    cs = jax.random.normal(jax.random.PRNGKey(30), (R_, D_))
    params = {"w": jnp.zeros(D_)}
    inner = sgd()
    op = ops.TopK(k=0.01)

    def grad_fn(p, data):
        c, noise = data
        g = p["w"] - c + 0.01 * noise
        return 0.5 * jnp.sum((p["w"] - c) ** 2), {"w": g}

    k = jax.random.PRNGKey(31)
    bs = []
    for _ in range(T_):
        k, s = jax.random.split(k)
        bs.append((cs, jax.random.normal(s, (R_, D_))))
    mask = schedule.fixed_schedule(T_, H_)
    step = engine.make_step(grad_fn, inner, op, constant(0.05), R_,
                            global_rounds=True)
    sstep = engine.make_superstep(grad_fn, inner, op, constant(0.05), R_,
                                  global_rounds=True)

    def host_loop():
        st = engine.init(params, inner, R_)
        st, _ = engine.run(st, step, bs, mask, jax.random.PRNGKey(32))
        return st.bits

    def superstep():
        st = engine.init(params, inner, R_)
        st, _ = engine.run_rounds(st, sstep, bs, mask,
                                  jax.random.PRNGKey(32))
        return st.bits

    rows = []
    for name, fn in (("host_loop", host_loop), ("superstep", superstep)):
        bits, us_total = _time(fn, n=5)
        us_step = us_total / T_
        rows.append(BenchRow(
            f"round/steps_per_s/{name}", us_step,
            f"steps_per_s={1e6 / max(us_step, 1e-9):.1f};H={H_};T={T_}",
            wire_bits=float(bits), path=name))

    # overlap vs serialized round driver (DESIGN.md §10): the same
    # schedule driven round-by-round (one dispatch + fetch per round)
    # vs windowed multiround programs (run_rounds_overlap: one scanned
    # program per window of up to 8 rounds, one fetch per window).  The
    # win is largest at H=1 — one round per step, so the serialized
    # driver pays a host round-trip per step — and the ledgers pin
    # bit-for-bit identity between the two drivers.
    for H in (1, 4, 8):
        m = schedule.fixed_schedule(T_, H)

        def serial(m=m):
            st = engine.init(params, inner, R_)
            st, _ = engine.run_rounds(st, sstep, bs, m,
                                      jax.random.PRNGKey(32))
            return st.bits

        def overlap(m=m):
            st = engine.init(params, inner, R_)
            st, _ = engine.run_rounds_overlap(st, sstep, bs, m,
                                              jax.random.PRNGKey(32))
            return st.bits

        pair = {}
        for name, fn in (("serial", serial), ("overlap", overlap)):
            bits, us_total = _time(fn, n=5)
            pair[name] = us_total / T_
            rows.append(BenchRow(
                f"round/overlap/H{H}/{name}", pair[name],
                f"steps_per_s={1e6 / max(pair[name], 1e-9):.1f};"
                f"H={H};T={T_}",
                wire_bits=float(bits), path=name))
        rows[-1].derived += (
            f";speedup={pair['serial'] / max(pair['overlap'], 1e-9):.2f}")
    return rows


def _bench_packing():
    """Megabuffer packing: one multi-leaf sync-round compression, packed
    (one kernel launch per operator-family bucket) vs leaf-by-leaf.
    Launches are counted at trace time; rounds/sec is the steady-state
    call rate of the jitted round."""
    tree = {
        f"layer{i}": jax.random.normal(jax.random.PRNGKey(40 + i),
                                       (128, 2048))
        for i in range(6)
    }
    tree["emb"] = jax.random.normal(jax.random.PRNGKey(50), (64, 4096))
    tree["head"] = jax.random.normal(jax.random.PRNGKey(51), (64, 4096))
    op = ops.TopK(k=0.01)
    d = int(sum(v.size for v in tree.values()))
    rows = []
    for pack in (True, False):
        cfg = dsp.DispatchConfig(mode="kernel", pack=pack)
        fn = jax.jit(lambda k, t, c=cfg: dsp.compress_tree(op, k, t, c))
        dsp.reset_launches()
        fn.lower(jax.random.PRNGKey(1), tree)  # trace -> count launches
        launches = dsp.total_launches()
        (out, bits), us = _time(fn, jax.random.PRNGKey(1), tree)
        rows.append(BenchRow(
            f"pack/sync_round/{'packed' if pack else 'per_leaf'}", us,
            f"launches_per_round={launches};"
            f"rounds_per_s={1e6 / max(us, 1e-9):.2f};"
            f"wire_ratio={float(bits) / (32 * d):.5f}",
            wire_bits=float(bits),
            path="packed" if pack else "per_leaf"))
    return rows


def _bench_hetero_policy():
    """Heterogeneous policy packing (DESIGN.md §6): one sync round of a
    per-leaf policy (Top_k matmuls, QSGD embeddings, dense norms)
    through the channel path, both directions.  Megabuffer packing must
    keep launches/round at one per operator *family* per direction —
    heterogeneous leaves bucket by family, not by leaf."""
    from repro.core import policy as pol
    from repro.core.channel import Channel

    tree = {
        "layers": {f"w{i}": jax.random.normal(jax.random.PRNGKey(80 + i),
                                              (128, 2048))
                   for i in range(6)},
        "embed": jax.random.normal(jax.random.PRNGKey(90), (64, 4096)),
        "head": jax.random.normal(jax.random.PRNGKey(91), (64, 4096)),
        "ln": jax.random.normal(jax.random.PRNGKey(92), (256,)),
    }
    spec = pol.parse(
        "ln->identity;embed|head->qsgd:s=15;.*->topk:k=0.01"
        " >> ln->identity;.*->topk:k=0.05")
    up_tree, down_tree = pol.as_channel_spec(spec).resolve(tree)
    d = int(sum(v.size for v in jax.tree_util.tree_leaves(tree)))
    rows = []
    for pack in (True, False):
        cfg = dsp.DispatchConfig(mode="kernel", pack=pack)
        up = Channel(up_tree, "uplink", cfg)
        down = Channel(down_tree, "downlink", cfg)

        def round_fn(key, acc):
            q, _m, b = up.apply(key, acc)
            q2, _m2, b2 = down.apply(jax.random.fold_in(key, 1), acc)
            return (q, q2), b + b2

        jfn = jax.jit(round_fn)
        dsp.reset_launches()
        jfn.lower(jax.random.PRNGKey(1), tree)
        launches = dict(dsp.LAUNCHES)
        (_, bits), us = _time(jfn, jax.random.PRNGKey(1), tree)
        if pack:
            # the acceptance gate: uplink topk + uplink qsgd +
            # downlink topk = one launch per family per direction
            assert launches["topk_compress"] == 2, launches
            assert launches["qsgd"] == 1, launches
        rows.append(BenchRow(
            f"policy/hetero_round/{'packed' if pack else 'per_leaf'}", us,
            f"launches_per_round={sum(launches.values())};"
            f"rounds_per_s={1e6 / max(us, 1e-9):.2f};"
            f"wire_ratio={float(bits) / (64 * d):.5f}",
            wire_bits=float(bits),
            path="packed" if pack else "per_leaf"))
    return rows


def _bench_channel_round():
    """Channel model (DESIGN.md §5): one sync round's compression cost
    and *total* wire bits, uplink-only (the pre-channel ledger, dense
    broadcast back) vs bidirectional (error-compensated Top_k on the
    downlink master delta too).  Launches are counted at trace time —
    megabuffer packing keeps one kernel launch per operator family per
    direction."""
    from repro.core.channel import Channel

    tree = {
        f"layer{i}": jax.random.normal(jax.random.PRNGKey(60 + i),
                                       (128, 2048))
        for i in range(6)
    }
    delta = {
        k: 0.1 * jax.random.normal(jax.random.PRNGKey(70 + i), v.shape)
        for i, (k, v) in enumerate(tree.items())
    }
    d = int(sum(v.size for v in tree.values()))
    cfg = dsp.DispatchConfig(mode="kernel")
    up = Channel(ops.TopK(k=0.01), "uplink", cfg)
    down = Channel(ops.TopK(k=0.05), "downlink", cfg)
    dense_down = float(32 * d)  # exact broadcast cost per receiver

    def uplink_only(key, acc):
        q, _mem, b = up.apply(key, acc)
        # the dense broadcast back is free compute but real wire cost
        return q, b + dense_down

    def bidirectional(key, acc, dacc):
        q, _mem, b = up.apply(key, acc)
        q2, _mem2, b2 = down.apply(jax.random.fold_in(key, 1), dacc)
        return (q, q2), b + b2

    rows = []
    for name, fn, fnargs in (
            ("uplink_only", uplink_only, (tree,)),
            ("bidirectional", bidirectional, (tree, delta))):
        jfn = jax.jit(fn)
        dsp.reset_launches()
        jfn.lower(jax.random.PRNGKey(1), *fnargs)
        launches = dsp.total_launches()
        (_, bits), us = _time(jfn, jax.random.PRNGKey(1), *fnargs)
        rows.append(BenchRow(
            f"channel/round/{name}", us,
            f"launches_per_round={launches};"
            f"wire_ratio={float(bits) / (64 * d):.5f}",
            wire_bits=float(bits), path="kernel"))
    return rows
