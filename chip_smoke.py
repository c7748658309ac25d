"""Smoke run of the main path on a TPU: yi-6b at its published widths.

    python chip_smoke.py                # one chip: kernels, train, parity, serve
    python chip_smoke.py --four-chips   # four chips: the data-parallel wires

One chip (the default) runs four phases in this one process:

  (k) kernels — the Top_k kernels (dense and compact) against their jnp
      oracles on one yi-6b-sized input: identical selections, counts,
      memories and (idx, val) buffers, and the compact buffers decode
      to the dense selection.
  (a) training — ``repro.launch.train.main`` on a 1x1 mesh: 8 steps of
      error-compensated Top_k (k = 1%, H = 4) with the Pallas kernels,
      writing a checkpoint.  Losses must be finite and the Top_k kernel
      must have launched.
  (a') parity — 2 steps, syncing every step, once with the kernels and
      once with ``--dispatch reference``.  The two are separate compiled
      programs whose bf16 gradients may round apart, so they are held
      to bounds: losses within 1e-4 relative, first-sync wire bits
      within 1 % (see ``phase_parity``).
  (b) serving — ``repro.launch.serve.main`` on that checkpoint,
      compressed (sparse-weight GEMMs), paged KV with the Pallas
      paged flash-decode, continuous batching: all 8 requests finish
      and nothing densifies.

``--four-chips`` runs only the paper's data-parallel path: a 4x1 mesh
(four workers), the same model and steps, over the dense psum wire and
then the compact sparse allgather wire.  The state must sit on all four
chips.  The two wires are separate compiled programs whose local steps
round apart on four chips, so they are held to measured bounds (see
``compare_wires``): per-step losses and cumulative wire bits, and the
final master parameters against the update training applied.

The depth is cut to ``LAYERS`` (widths, heads, vocabulary untouched):
compiled for v5e, the train round program at 2 layers needs 12.40 GiB
on one chip (13.58-14.09 GiB per chip on four), at 3 layers 15.48 GiB.
The parity phase runs at 1 layer: the reference path's whole-leaf f32
temporaries need 15.28 GiB at 2.

Exits nonzero without printing a result when JAX finds no TPU.  The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

LAYERS = 2
#: where the smoke keeps its checkpoint (gitignored, removed at the end)
OUT = ROOT / ".smoke_out"


def log(*args):
    print(*args, flush=True)


def train_args(layers: int, mesh: str, steps: int, H: int, *extra):
    return ["--arch", "yi-6b", "--layers", str(layers), "--mesh", mesh,
            "--seq", "2048", "--batch", "1", "--steps", str(steps),
            "--H", str(H), "--policy", "topk:k=0.01", *extra]


def phase_train(train):
    import numpy as np
    ck = OUT / "ck"
    t = time.time()
    res = train.main(train_args(LAYERS, "1x1", 8, 4, "--dispatch", "auto",
                                "--ckpt", str(ck)))
    log(f"[train] layers={res['layers']} steps={len(res['losses'])} "
        f"seconds={time.time() - t:.1f} launches={res['launches']}")
    log(f"[train] losses={res['losses']}")
    log(f"[train] bits_up={res['bits_up']}")
    if not np.all(np.isfinite(res["losses"])):
        raise SystemExit(f"non-finite training loss: {res['losses']}")
    if res["launches"].get("topk_compress", 0) <= 0:
        raise SystemExit("the Top_k kernel never launched: 'auto' dispatch "
                         f"did not mean kernels ({res['launches']})")
    return ck


def phase_kernels():
    """The Top_k kernels against their jnp oracles on one input, through
    the dispatch entry points the engine calls: one [4096, 4096] leaf
    (a yi-6b projection, rows of d_model, k = 1 %) holding an
    error-compensated accumulator as training makes it, f32 memory plus
    a bf16 step, so magnitudes tie.  Selections, counts, memories and
    the compact (idx, val) buffers must be identical, and the compact
    buffers must decode to the dense kernel's selection."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.operators import resolve_k
    from repro.kernels import dispatch as dsp
    from repro.kernels.ref import topk_compact_ref, topk_compress_ref
    n = 4096
    k = resolve_k(0.01, n)
    kcap = dsp.capacity(k, n)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    step = (1e-3 * jax.random.normal(k1, (n, n))).astype(jnp.bfloat16)
    acc = 1e-4 * jax.random.normal(k2, (n, n)) + step.astype(jnp.float32)
    cfg = dsp.DispatchConfig(mode="kernel")
    kern = jax.jit(lambda a: dsp.topk_rows(a, k, cfg=cfg))(acc)
    ref = jax.jit(lambda a: topk_compress_ref(a, k))(acc)
    ckern = jax.jit(lambda a: dsp.compact_rows(a, k, kcap, cfg=cfg))(acc)
    cref = jax.jit(lambda a: topk_compact_ref(a, k, kcap))(acc)
    dec = jax.jit(lambda i, v: dsp.decode_rows(i, v, n))(ckern[0], ckern[1])
    kern, ref, ckern, cref, dec = jax.device_get((kern, ref, ckern, cref, dec))
    checks = {
        "compress sel": np.array_equal(kern[0], ref[0]),
        "compress mem": np.array_equal(kern[1], ref[1]),
        "compress cnt": np.array_equal(kern[2], ref[2]),
        "compact idx": np.array_equal(ckern[0], cref[0]),
        "compact val": np.array_equal(ckern[1], cref[1]),
        "compact mem": np.array_equal(ckern[2], cref[2]),
        "compact cnt": np.array_equal(ckern[3], cref[3]),
        "decode == compress sel": np.array_equal(dec, kern[0]),
    }
    log(f"[kernels] {n}x{n} k={k} kcap={kcap}: survivors kernel "
        f"{int(kern[2].sum())} reference {int(ref[2].sum())} compact "
        f"{int(ckern[3].sum())} (k x rows = {k * n}); identical: {checks}")
    bad = [name for name, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"kernels: kernel and oracle differ on {bad}")


#: parity bounds between the kernel and the reference dispatch, which
#: compile to different programs: their step-1 bf16 gradients may round
#: apart, and where a row's k-th magnitude ties (bf16 steps tie often)
#: the tie decides how many survive.  Measured on a v5e: losses 6.5e-7
#: relative apart, bits 0.36 %.  A wrong selection or decode moves them
#: much further (the compact decode's bf16 matmul bug moved the loss by
#: 5e-3 to 6e-2 relative); with the same input the kernels are exact
#: (phase_kernels).
LOSS_RTOL = 1e-4
BITS_RTOL = 1e-2


def phase_parity(train):
    args = train_args(1, "1x1", 2, 1)
    kern = train.main(args + ["--dispatch", "auto"])
    ref = train.main(args + ["--dispatch", "reference"])
    loss_rel = max(abs(a - b) / abs(b)
                   for a, b in zip(kern["losses"], ref["losses"]))
    bits_rel = abs(kern["bits_up"][0] - ref["bits_up"][0]) / ref["bits_up"][0]
    log(f"[parity] kernel losses={kern['losses']} reference losses="
        f"{ref['losses']} max relative diff={loss_rel} (bound {LOSS_RTOL})")
    log(f"[parity] kernel bits={kern['bits_up']} reference bits="
        f"{ref['bits_up']} step-1 relative diff={bits_rel} (bound "
        f"{BITS_RTOL}); kernel launches={kern['launches']} reference "
        f"launches={ref['launches']}")
    if kern["launches"].get("topk_compress", 0) <= 0:
        raise SystemExit("parity: the kernel run launched no Top_k kernel")
    if any(ref["launches"].values()):
        raise SystemExit("parity: the reference run launched kernels")
    if loss_rel > LOSS_RTOL:
        raise SystemExit(f"parity: kernel and reference losses differ by "
                         f"{loss_rel} relative > {LOSS_RTOL}")
    if bits_rel > BITS_RTOL:
        raise SystemExit(f"parity: kernel and reference step-1 wire bits "
                         f"differ by {bits_rel} relative > {BITS_RTOL}")


def phase_serve(serve, ck):
    from repro.kernels.dispatch import LAUNCHES, reset_launches
    reset_launches()
    t = time.time()
    res = serve.main([
        "--arch", "yi-6b", "--layers", str(LAYERS), "--checkpoint", str(ck),
        "--compressed", "--paged", "--flash", "--page-size", "32",
        "--scheduler", "continuous", "--requests", "8", "--max-batch", "8",
        "--prompt-len", "128", "--new-tokens", "16", "--max-len", "160"])
    launches = {k: v for k, v in LAUNCHES.items() if v}
    log(f"[serve] seconds={time.time() - t:.1f} finished={res['finished']} "
        f"new_tokens={res['new_tokens']} densify={res['densify']} "
        f"steps={res['steps']} launches={launches}")
    if res["densify"] != 0:
        raise SystemExit(f"serving densified {res['densify']} leaves")
    if res["finished"] != 8 or res["new_tokens"] != [16] * 8:
        raise SystemExit(f"not every request finished: {res}")
    for kernel in ("sparse_gemm", "paged_decode"):
        if launches.get(kernel, 0) <= 0:
            raise SystemExit(f"serving never launched {kernel}")


def four_chips(train):
    import jax
    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX sees "
                         f"{len(jax.devices())}")
    args = train_args(LAYERS, "4x1", 8, 4, "--dispatch", "auto")
    runs = {}
    for wire in ("dense_psum", "sparse_allgather"):
        t = time.time()
        res = runs[wire] = train.main(args + ["--wire", wire],
                                      return_master=True)
        log(f"[4x1 {wire}] seconds={time.time() - t:.1f} "
            f"launches={res['launches']}")
        log(f"[4x1 {wire}] losses={res['losses']}")
        log(f"[4x1 {wire}] bits_up={res['bits_up']}")
        log(f"[4x1 {wire}] state_devices={res['state_devices']} "
            f"worker_shard_devices={res['worker_shard_devices']} "
            f"worker_shard_shape={res['worker_shard_shape']}")
        if res["workers"] != 4 or res["state_devices"] != [0, 1, 2, 3] or \
                len(set(res["worker_shard_devices"])) != 4 or \
                res["worker_shard_shape"][0] != 1:
            raise SystemExit(f"{wire}: the state is not spread over the "
                             f"four chips")
        if res["launches"].get(
                "topk_compress" if wire == "dense_psum" else "topk_compact",
                0) <= 0:
            raise SystemExit(f"{wire}: no Top_k kernel launched")
    compare_wires(runs["dense_psum"], runs["sparse_allgather"])


#: bounds between the two 4x1 wires.  They are separate compiled
#: programs, and on four v5e chips their local steps already round
#: apart before any exchange (step-2 losses 11.415734 vs 11.415724).
#: Measured after the repair of the compact scatter (my chip run): bits
#: 1.05e-6 relative apart, losses 1.7e-4 relative, final master 0.082 x
#: the update apart.  A wrong sparse decode is far outside: before the
#: repair the losses parted by 6e-2 relative after the first sync, and
#: on one chip the master by 1.28 x the update.
WIRE_BITS_RTOL = 1e-4
WIRE_LOSS_RTOL = 1e-3
WIRE_MASTER_GAP = 0.25


def compare_wires(dense, sparse):
    rel, max_diff, max_upd = update_gap(dense["master"], sparse["master"],
                                        dense["master0"])
    loss_rel = max(abs(a - b) / abs(a)
                   for a, b in zip(dense["losses"], sparse["losses"]))
    bits_rel = max(abs(a - b) / a
                   for a, b in zip(dense["bits_up"], sparse["bits_up"]) if a)
    log(f"[4x1] identical: losses={dense['losses'] == sparse['losses']} "
        f"bits={dense['bits_up'] == sparse['bits_up']} master={max_diff == 0}"
        f"; relative gaps: losses {loss_rel} (bound {WIRE_LOSS_RTOL}), "
        f"bits {bits_rel} (bound {WIRE_BITS_RTOL}), master |dense - sparse|"
        f" / |update| {rel} (bound {WIRE_MASTER_GAP}); max |master diff| "
        f"{max_diff}, max |update| {max_upd}")
    if bits_rel > WIRE_BITS_RTOL:
        raise SystemExit(f"the two wires' bits differ by {bits_rel} relative")
    if loss_rel > WIRE_LOSS_RTOL:
        raise SystemExit(f"the two wires' losses differ by {loss_rel} "
                         f"relative")
    if rel > WIRE_MASTER_GAP:
        raise SystemExit(f"the two wires' masters differ by {rel} x the "
                         f"update")


def update_gap(a, b, m0):
    """How far two runs' final masters ``a`` and ``b`` part, against the
    update ``a - m0`` that training applied: (||a - b|| / ||a - m0||
    over all leaves, max |a - b|, max |a - m0|)."""
    import jax
    import numpy as np
    num = den = max_diff = max_upd = 0.0
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, m0))):
        x, y, z = (np.asarray(v, np.float32) for v in (x, y, z))
        num += float(np.square(x - y).sum())
        den += float(np.square(x - z).sum())
        max_diff = max(max_diff, float(np.abs(x - y).max()))
        max_upd = max(max_upd, float(np.abs(x - z).max()))
    return (num / den) ** 0.5, max_diff, max_upd


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1 dense-psum vs sparse-allgather "
                         "comparison (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(1)
    from repro.launch import serve, train
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.device_kind} x{len(jax.devices())}; compile cache: "
        f"{enable_compile_cache()}")
    t0 = time.time()
    try:
        if args.four_chips:
            four_chips(train)
        else:
            phase_kernels()
            ck = phase_train(train)
            phase_parity(train)
            phase_serve(serve, ck)
    finally:
        shutil.rmtree(OUT, ignore_errors=True)
    stats = dev.memory_stats() or {}
    log(f"total seconds={time.time() - t0:.1f} peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
