"""Production multi-pod engine for Qsparse-local-SGD.

Mapping onto the TPU mesh (see DESIGN.md §4):

  * Qsparse worker r  <->  one (pod, data) mesh row.  ``R = pod * data``.
  * tensor parallelism lives on the 'model' axis and is left to XLA SPMD:
    we shard_map with ``axis_names={'pod','data'}`` (manual) only.
  * the compressed aggregation  x_{t+1} = x_t - (1/R) sum_r g_r  is an
    explicit ``psum`` over the manual axes — the only cross-worker
    communication the algorithm performs.
  * compression is applied **per model shard** (each worker compresses
    the slice of each leaf it owns together with its TP group): we pick
    the top-k axis per leaf to be an *unsharded* axis so XLA keeps
    lax.top_k shard-local — this is Corollary 1 (piecewise compression)
    across shards; no gather enters the compression path.

Two statically-specialized step functions are built:

  * ``local_step``  — Algorithm-1 lines 5-7 (no communication beyond TP)
  * ``sync_step``   — lines 8-11 + master update (compressed psum)

The host trainer drives the schedule (``I_T``), which also keeps
collectives out of lax.cond and makes the dry-run/roofline artifacts
cleanly separable per step kind.

State layout (leading axes refer to the *global* array view):

  master : params pytree; replicated over ('pod','data') by default, or
           ZeRO-1-sharded over ('pod','data') on axis 0 when zero1=True
           (beyond-paper optimization, §Perf).
  local / memory / inner : one leading worker axis of size R, sharded
           P(('pod','data')) — physically one replica per worker.
  view / down_memory : same worker layout; only with a compressed
           ``downlink=`` channel (DESIGN.md §5) — each worker's lagging
           master view and the server-side downlink error memory.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import bits as bitlib
from repro.core import channel as chn
from repro.core.operators import (
    CompressionOp,
    Identity,
    RowSignTopK,
    RowTopK,
    SignSparsifier,
    TopK,
    ops_for_leaves,
    resolve_k,
)
from repro.optim.transforms import GradientTransform, apply_updates


class DistQsparseState(NamedTuple):
    master: Any
    local: Any            # leading worker axis R
    memory: Any           # leading worker axis R
    inner: Any            # leading worker axis R
    step: jnp.ndarray
    bits: jnp.ndarray     # uplink wire bits (worker → server)
    rounds: jnp.ndarray
    # downlink channel state (DESIGN.md §5) — populated only with a
    # compressed ``downlink=`` ShardCompressor in make_dist_steps:
    # view is x_t^{(r)} (each worker's lagging copy of the master),
    # down_memory the server-side per-worker error memory md^{(r)}
    view: Any = None          # leading worker axis R
    down_memory: Any = None   # leading worker axis R
    bits_down: Any = None     # downlink wire bits (server → worker)
    # staleness-first fault runtime (DESIGN.md §9) — populated only by
    # make_dist_fault_steps: the bounded per-worker in-flight payload
    # queue.  Dense wire: a master-shaped pytree of [R, depth, ...]
    # buffers; sparse wire: the compact (idx, val) wire buffers per
    # leaf, [R, depth, ..., kcap].  arrive_at[r, s] is the global step
    # at which slot s lands on the master (-1 = empty), inflight_tau
    # its staleness τ.
    inflight: Any = None
    arrive_at: Any = None     # int32 [R, depth]
    inflight_tau: Any = None  # int32 [R, depth]


#: the ``jax.named_scope`` of each stage of the round program built by
#: ``make_dist_steps``.  Every device operation of a stage carries its
#: scope in the HLO ``op_name`` (``.../qsparse.grad/transpose(jvp())/
#: dot_general``) and so in a profiler trace's ``tf_op``; scopes never
#: nest, so an operation falls under at most one.  Metadata only: the
#: compiled arithmetic is the same with or without them.
ROUND_SCOPES = (
    "qsparse.grad",           # model forward and backward
    "qsparse.optimizer",      # learning rate, momentum, half step
    "qsparse.compress",       # uplink accumulator, Top_k, masking
    "qsparse.exchange",       # cross-worker collectives
    "qsparse.decode",         # sparse wire: compact buffers -> dense mean
    "qsparse.master_update",  # new master and its broadcast
    "qsparse.downlink",       # compressed server -> worker channel
)


# ---------------------------------------------------------------------------
# shard-local compression
# ---------------------------------------------------------------------------


def _pick_axis(shape: tuple[int, ...], spec: Optional[P]) -> int:
    """First axis not sharded by 'model' (prefer the last one)."""
    if spec is None:
        return len(shape) - 1
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def uses_model(e):
        if e is None:
            return False
        if isinstance(e, (tuple, list)):
            return "model" in e
        return e == "model"

    for ax in range(len(shape) - 1, -1, -1):
        if not uses_model(entries[ax]) and shape[ax] > 1:
            return ax
    return len(shape) - 1


def axis_topk_compact(x: jnp.ndarray, k_frac: float, axis: int,
                      sign_bits: bool = False, dispatch_cfg=None):
    """Top-k along ``axis`` in *compact* form (DESIGN.md §3.3).

    Returns (idx [..., kcap] int32, val [..., kcap] f32, mem — the
    fused error memory in ``x``'s layout (f32) —, wire_bits,
    moved_shape) where idx/val live on the moved-to-last layout, with
    per-row indices relative to that last axis and empty slots holding
    the out-of-row sentinel (idx = n, val = 0).  Shard-local by
    construction when ``axis`` is unsharded.

    Sort-free on both routes: the compact Pallas kernel when the row
    is eligible (``dispatch_cfg``), else the scatter-free jnp oracle.

    Wire bits are *counted* from the actual survivors (exact zeros
    excluded), matching the dense path's ledger convention.
    """
    from repro.kernels import dispatch as dsp
    n = x.shape[axis]
    k = resolve_k(k_frac, n)
    kcap = dsp.capacity(k, n)
    xm = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    rows = xm.reshape(-1, n)
    idx, val, mem, cnt = dsp.compact_rows(
        rows, k, kcap, sign=sign_bits, cfg=dispatch_cfg, leaf_size=x.size)
    nrows = rows.shape[0]
    counted = (bitlib.bits_signtopk_counted if sign_bits
               else bitlib.bits_topk_counted)
    bits = (jnp.float32(32 * nrows) + counted(n, jnp.sum(cnt))
            - jnp.float32(32))
    idx = idx.reshape(xm.shape[:-1] + (kcap,))
    val = val.reshape(xm.shape[:-1] + (kcap,))
    mem = jnp.moveaxis(mem.reshape(xm.shape), -1, axis)
    return idx, val, mem, bits, xm.shape


def _densify(idx, sel, moved_shape, axis):
    """Dense decode of compact (idx, sel) buffers on the moved layout —
    dispatch.decode_rows per compression row (sentinel slots drop, so
    fixed-capacity buffers decode without a length field)."""
    from repro.kernels.dispatch import decode_rows
    kcap = idx.shape[-1]
    out = decode_rows(idx.reshape(-1, kcap), sel.reshape(-1, kcap),
                      moved_shape[-1])
    return jnp.moveaxis(out.reshape(moved_shape), -1, axis)


def _threshold_axis_topk(x: jnp.ndarray, k_frac: float, axis: int,
                         sign_bits: bool, select):
    """Shared dense Top_k-along-axis plumbing: move ``axis`` last, shape
    [rows, n], run ``select(rows2d, k, sign) -> (sel, mem, cnt)`` (the
    Pallas kernel or its jnp oracle), move back, charge counted bits."""
    n = x.shape[axis]
    k = resolve_k(k_frac, n)
    xm = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    rows = xm.reshape(-1, n)
    sel, _mem, cnt = select(rows, k, sign_bits)
    out = jnp.moveaxis(sel.reshape(xm.shape), -1, axis)
    nrows = rows.shape[0]
    counted = (bitlib.bits_signtopk_counted if sign_bits
               else bitlib.bits_topk_counted)
    bits = (jnp.float32(32 * nrows) + counted(n, jnp.sum(cnt))
            - jnp.float32(32))
    return out, bits


def axis_topk(x: jnp.ndarray, k_frac: float, axis: int,
              sign_bits: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Dense Top_k along ``axis`` via the bisection *threshold select*
    (kernels/ref.py; exact-k generically — DESIGN.md §3.1).

    Sort- and scatter-free on purpose: on TPU the threshold form is the
    fast path (§3)."""
    from repro.kernels.ref import topk_compress_ref
    return _threshold_axis_topk(
        x, k_frac, axis, sign_bits,
        lambda rows, k, sign: topk_compress_ref(rows, k, sign=sign))


@dataclasses.dataclass(frozen=True)
class ShardCompressor:
    """Leafwise shard-local compressor for the distributed engine.

    mode: 'topk' (full-precision survivors) | 'signtopk' (1-bit survivors)
          | 'none' (Identity — vanilla/local-SGD baselines)
          | 'policy' (heterogeneous per-leaf operators, DESIGN.md §6:
          ``ops`` carries the resolved operator tree — build through
          :meth:`from_spec`)
    k_frac: survivor fraction along the chosen axis per leaf
          (homogeneous modes only; policy mode reads each op's own k).
    dispatch: kernel routing policy (see kernels/dispatch.py) — 'auto'
          runs the fused Pallas Top_k kernels on TPU for lane-aligned
          compression rows, 'kernel' forces them (interpret off-TPU),
          'reference' keeps the pure-jnp threshold path.  Both the
          dense form (``__call__``) and the compact wire form
          (``compact``) dispatch: the compact-emitting kernel writes
          (idx, val) survivor buffers plus the fused error memory
          directly (DESIGN.md §3.3), with the scatter-free jnp oracle
          as its transparent fallback.
    row_axes: the mesh axes the engine's worker-manual region leaves
          Auto (set by the engine builders, :func:`_bind_mesh`): the
          row kernels split their rows over them.
    ops:  policy mode only — a ``CompressionOp`` tree (or single op) in
          the grads' structure, as produced by ``core.policy.resolve``.
          Per leaf: Top_k-family ops run the shard-local axis-Top_k
          paths (sparse wire form available; op.k is the survivor
          fraction/count along the chosen axis), Identity transmits
          dense, and every other operator (QSGD, k-level, Rand_k, the
          composed sparsifiers) runs its reference form shard-locally
          on the leaf and travels as a dense payload — Corollary 1
          piecewise compression across shards either way.
    """

    mode: str = "topk"
    k_frac: float = 0.01
    dispatch: str = "auto"
    ops: Any = None
    row_axes: tuple = ()

    @classmethod
    def from_spec(cls, spec, params,
                  dispatch: str = "auto") -> "Optional[ShardCompressor]":
        """Build from any ``core.policy`` spec (PolicySpec/OpSpec/DSL
        string/operator tree), resolved per leaf against ``params``.
        Returns None for an all-Identity policy (= no compression).

        The shard paths select Top_k per compression *row* (the chosen
        unsharded axis), so a global-Top_k op with an **absolute** k —
        a whole-leaf survivor count, e.g. from the budget allocator —
        is normalized here to the equivalent leaf fraction ``k / d``
        (the per-row counts then sum back to ~k across the leaf's rows
        instead of selecting k per row, §6.4).  Fractional k and the
        per-row ops (RowTopK/RowSignTopK, whose k is per-row by
        definition) pass through untouched.
        """
        from repro.core import policy as pol
        op_tree = pol.resolve(spec, params)
        leaves = jax.tree_util.tree_leaves(params)
        ops_list = ops_for_leaves(op_tree, len(leaves))
        norm = [cls._normalize_leaf_op(op, int(leaf.size))
                for op, leaf in zip(ops_list, leaves)]
        op_tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), norm)
        comp = cls(mode="policy", dispatch=dispatch, ops=op_tree)
        return None if comp.is_identity() else comp

    @staticmethod
    def _normalize_leaf_op(op: CompressionOp, d: int) -> CompressionOp:
        """Absolute whole-leaf k → leaf fraction for global-Top_k ops
        (see :meth:`from_spec`).  ``1 − 1e-9`` keeps an everything-
        survives k inside resolve_k's fraction regime."""
        if not (isinstance(op, TopK) or (isinstance(op, SignSparsifier)
                                         and op.sparsifier == "top")):
            return op
        if isinstance(op.k, float) and 0.0 < op.k < 1.0:
            return op
        frac = min(1.0 - 1e-9, float(op.k) / max(d, 1))
        return dataclasses.replace(op, k=frac)

    def is_identity(self) -> bool:
        if self.mode == "none":
            return True
        if self.mode != "policy":
            return False
        leaves = jax.tree_util.tree_leaves(
            self.ops, is_leaf=lambda o: isinstance(o, CompressionOp))
        return all(isinstance(o, Identity) for o in leaves)

    def _dispatch_cfg(self):
        from repro.kernels.dispatch import DispatchConfig
        return DispatchConfig(mode=self.dispatch, row_axes=self.row_axes)

    def interprets_kernels(self) -> bool:
        """True where the Top_k kernels would run in interpret mode
        (off-TPU with ``dispatch="kernel"``)."""
        cfg = self._dispatch_cfg()
        return cfg.kernels_enabled() and cfg._interpret()

    def _plans(self, n_leaves: int):
        """Per-leaf execution plan: ("skip",), ("axis", k, sign_bits)
        or ("ref", op) — shared by the dense path, the compact path and
        the payload-kind metadata so all three always agree."""
        if self.mode == "policy":
            plans = []
            for op in ops_for_leaves(self.ops, n_leaves):
                if isinstance(op, Identity):
                    plans.append(("skip",))
                elif isinstance(op, (TopK, RowTopK)):
                    plans.append(("axis", op.k, False))
                elif isinstance(op, RowSignTopK) or (
                        isinstance(op, SignSparsifier)
                        and op.sparsifier == "top"):
                    plans.append(("axis", op.k, True))
                else:
                    plans.append(("ref", op))
            return plans
        if self.mode == "none":
            return [("skip",)] * n_leaves
        if self.mode not in ("topk", "signtopk"):
            raise ValueError(
                f"unknown ShardCompressor mode {self.mode!r}; expected "
                f"'topk' | 'signtopk' | 'none' | 'policy'")
        return [("axis", self.k_frac, self.mode == "signtopk")] * n_leaves

    @staticmethod
    def _skip(g) -> bool:
        """Tiny/scalar leaves transmit dense regardless of plan."""
        return g.ndim == 0 or g.size <= 8

    def _ref_leaf(self, op: CompressionOp, g, key, i: int):
        """Reference-operator leaf (dense payload): shard-local
        ``op(key_i, g)``.  Stochastic ops draw from ``key`` folded with
        the leaf index; the key is replicated over the worker axes, so
        the draw is shared across workers (the accumulators differ, so
        per-worker unbiasedness is unaffected)."""
        if op.stochastic and key is None:
            raise ValueError(
                f"stochastic operator {type(op).__name__} in a "
                f"ShardCompressor policy needs a key (thread key= "
                f"through apply/compact)")
        k_i = jax.random.fold_in(key, i) if op.stochastic else None
        out, b = op(k_i, g)
        return out.astype(jnp.float32), jnp.asarray(b, jnp.float32)

    def _kernel_leaf(self, g, k_frac, ax, sign):
        """Fused-kernel variant of ``axis_topk`` (dense survivors)."""
        from repro.kernels import dispatch as dsp
        cfg = self._dispatch_cfg()
        return _threshold_axis_topk(
            g, k_frac, ax, sign,
            lambda rows, k, sign_: dsp.topk_rows(rows, k, sign=sign_,
                                                 cfg=cfg))

    def __call__(self, grads, param_specs, key=None):
        from repro.kernels import dispatch as dsp
        dcfg = self._dispatch_cfg()
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        specs = self._leaf_specs(param_specs, len(leaves))
        plans = self._plans(len(leaves))
        outs, bit_terms = [], []
        for i, (g, spec, plan) in enumerate(zip(leaves, specs, plans)):
            if plan[0] == "skip" or self._skip(g):
                outs.append(g.astype(jnp.float32))
                bit_terms.append(jnp.asarray(bitlib.bits_dense(g.size), jnp.float32))
                continue
            if plan[0] == "ref":
                o, b = self._ref_leaf(plan[1], g, key, i)
                outs.append(o)
                bit_terms.append(b)
                continue
            _, k_frac, sign = plan
            ax = _pick_axis(g.shape, spec)
            if dsp.rows_eligible(g.shape[ax], dcfg, leaf_size=g.size):
                o, b = self._kernel_leaf(g, k_frac, ax, sign)
            else:
                o, b = axis_topk(g, k_frac, ax, sign_bits=sign)
            if spec is not None:
                # pin the densified update to the leaf's TP sharding: the
                # top_k/scatter pair otherwise makes XLA re-shard (an
                # all-gather per leaf — §Perf iteration 2 finding)
                entries = list(spec) + [None] * (g.ndim - len(tuple(spec)))
                o = jax.lax.with_sharding_constraint(o, P(*entries))
            outs.append(o)
            bit_terms.append(b)
        bits = jnp.sum(jnp.stack(bit_terms))
        return jax.tree_util.tree_unflatten(treedef, outs), bits

    def _leaf_specs(self, param_specs, n):
        if param_specs is None:
            return [None] * n
        return jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda z: isinstance(z, P) or z is None
        )

    def compact(self, grads, param_specs, key=None):
        """Compress to the compact wire form (§Perf beyond-paper
        aggregation): per leaf either ("dense", g) for skipped /
        reference-operator leaves (the latter carry the *compressed*
        dense payload) or ("sparse", idx, val, axis, moved_shape), with
        indices row-local to the moved-to-last compression axis
        (shard-local offsets — the model-sharded axes never enter the
        index space) and empty slots carrying the out-of-row sentinel.
        The fused error memories ride along so the sync body never
        densifies.

        Returns (list_of_leaf_payloads, treedef, wire_bits, mem_tree).
        """
        dcfg = self._dispatch_cfg()
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        specs = self._leaf_specs(param_specs, len(leaves))
        plans = self._plans(len(leaves))
        payloads, bit_terms, mems = [], [], []
        for i, (g, spec, plan) in enumerate(zip(leaves, specs, plans)):
            if plan[0] == "skip" or self._skip(g):
                g32 = g.astype(jnp.float32)
                payloads.append(("dense", g32))
                mems.append(jnp.zeros_like(g32))
                bit_terms.append(
                    jnp.asarray(bitlib.bits_dense(g.size), jnp.float32))
                continue
            if plan[0] == "ref":
                q, b = self._ref_leaf(plan[1], g, key, i)
                payloads.append(("dense", q))
                mems.append(g.astype(jnp.float32) - q)
                bit_terms.append(b)
                continue
            _, k_frac, sign = plan
            ax = _pick_axis(g.shape, spec)
            idx, val, mem, b, moved = axis_topk_compact(
                g, k_frac, ax, sign_bits=sign, dispatch_cfg=dcfg)
            payloads.append(("sparse", idx, val, ax, moved))
            mems.append(mem)
            bit_terms.append(b)
        bits = jnp.sum(jnp.stack(bit_terms))
        mem_tree = jax.tree_util.tree_unflatten(treedef, mems)
        return payloads, treedef, bits, mem_tree

    def leaf_meta(self, master_tree, param_specs):
        """Payload-kind metadata per leaf, mirroring :meth:`compact`'s
        decisions on the *global* leaf shapes: ("sparse", axis,
        moved_shape) for axis-Top_k leaves, ("dense", None, None) for
        everything else.  The sparse sync bodies size their out_specs
        from this, so it must stay in lockstep with compact()."""
        leaves = jax.tree_util.tree_flatten(master_tree)[0]
        specs = self._leaf_specs(param_specs, len(leaves))
        plans = self._plans(len(leaves))
        meta = []
        for g, spec, plan in zip(leaves, specs, plans):
            if plan[0] != "axis" or self._skip(g):
                meta.append(("dense", None, None))
                continue
            ax = _pick_axis(g.shape, spec)
            moved = jnp.moveaxis(
                jnp.empty(g.shape, jnp.float32), ax, -1).shape
            meta.append(("sparse", ax, moved))
        return meta

    def gamma(self) -> float:
        if self.mode == "policy":
            gs = []
            for op in jax.tree_util.tree_leaves(
                    self.ops, is_leaf=lambda o: isinstance(o, CompressionOp)):
                if isinstance(op, Identity):
                    gs.append(1.0)
                elif hasattr(op, "k") and isinstance(op.k, float) \
                        and 0.0 < op.k < 1.0:
                    gs.append(op.k)
                else:
                    gs.append(0.0)  # unknown/absolute-k: conservative
            return min(gs) if gs else 1.0
        return 1.0 if self.mode == "none" else self.k_frac


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def worker_count(mesh, data_axes: Sequence[str]) -> int:
    out = 1
    for a in data_axes:
        out *= mesh.shape[a]
    return out


def state_shardings(mesh, data_axes: Sequence[str], param_specs, state_tree):
    """NamedShardings for DistQsparseState (for jit in_shardings / init)."""
    daxes = tuple(data_axes)

    def master_spec(spec):
        return spec if spec is not None else P()

    def worker_spec(spec):
        inner = tuple(spec) if spec is not None else ()
        return P(daxes, *inner)

    master = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, master_spec(s)), param_specs,
        is_leaf=lambda z: isinstance(z, P) or z is None,
    )
    worker = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, worker_spec(s)), param_specs,
        is_leaf=lambda z: isinstance(z, P) or z is None,
    )
    return master, worker


def _checks_vma(*comps: Optional[ShardCompressor]) -> bool:
    """``check_vma`` for the engine's shard_map regions: on, except
    around kernels run in interpret mode, whose loops the interpreter
    replays as untyped jnp (compiled kernels type their outputs)."""
    return not any(c is not None and c.interprets_kernels() for c in comps)


def _bind_mesh(comp: Optional[ShardCompressor], mesh,
               daxes: tuple) -> Optional[ShardCompressor]:
    """``comp`` with the mesh axes the worker-manual region leaves Auto
    (every non-worker axis) as its kernels' row-split axes."""
    if comp is None:
        return None
    return dataclasses.replace(
        comp, row_axes=tuple(a for a in mesh.axis_names if a not in daxes))


def make_dist_steps(
    grad_fn: Callable,                 # (params, batch) -> (loss, grads)
    inner_opt: GradientTransform,
    compressor: ShardCompressor,
    lr_schedule: Callable,
    mesh,
    data_axes: Sequence[str] = ("data",),
    param_specs=None,                  # pytree of P for leaves (model axis)
    zero1: bool = False,
    aggregate: str = "mean_R",         # master division rule (DESIGN.md §8)
    downlink: Optional[ShardCompressor] = None,
    wire: str = "dense_psum",          # "dense_psum" | "sparse_allgather"
    partial: bool = False,
):
    """Returns (init_fn, local_step, sync_step).

    ``batch`` leaves carry a leading worker axis R sharded over
    data_axes.  Inside the manual region every worker sees leading dim 1.

    ``downlink``: server→worker compression channel (DESIGN.md §5) — a
    second ShardCompressor applied to each worker's master delta
    ``x̄_{t+1} − x_t^{(r)}`` against a server-side per-worker error
    memory before the broadcast; the worker's view (= its post-sync
    local iterate) then advances by the decompressed delta only, and
    the uplink compresses against that lagging view.  None (or mode
    "none") keeps the exact dense broadcast — bit-for-bit today's
    trajectories — while charging its dense cost to ``bits_down``.

    ``wire``: the sync round's wire format — "dense_psum" (in-body
    pmean ring all-reduce) or "sparse_allgather" (compact (idx, val)
    survivor buffers leave the manual region, dense decode in the auto
    region; DESIGN.md §3.3).  Identical math and ledger either way.

    ``aggregate``: the master's division rule over the syncing subset
    (DESIGN.md §8) — "mean_R" (the paper's Σ/R, bit-for-bit historical),
    "mean_S" (divide by |S|), or "support_weighted" (per-coordinate
    survivor counts with the zero-support guard).  For backward
    compatibility a wire-format value passed here is remapped onto
    ``wire=`` with a one-time warning.

    ``partial``: accept per-step participation masks (fleet scenarios,
    ``core/scenarios.py``) — ``sync_step``/``round_fn`` then take a
    trailing ``sync_mask`` bool[R] argument; workers with a False bit
    contribute nothing, keep their error memory, and continue from
    their own half-step iterate against a lagging master *view* (the
    state carries ``view`` even without a downlink).  With
    ``partial=False`` nothing changes: no extra state, bit-for-bit
    today's trajectories.
    """
    from repro.core import policy as pol
    from repro.core.scenarios import validate_aggregate
    if aggregate in ("dense_psum", "sparse_allgather"):
        pol.warn_once(
            "dist-aggregate-wire",
            "aggregate= now names the aggregation rule ('mean_R' | "
            "'mean_S' | 'support_weighted'); wire formats moved to "
            f"wire=. Mapping aggregate={aggregate!r} to wire= with "
            "aggregate='mean_R' (the historical behaviour).")
        wire, aggregate = aggregate, "mean_R"
    validate_aggregate(aggregate)
    if wire not in ("dense_psum", "sparse_allgather"):
        raise ValueError(f"unknown wire {wire!r}; expected 'dense_psum' "
                         f"| 'sparse_allgather'")
    daxes = tuple(data_axes)
    R = worker_count(mesh, daxes)
    manual = set(daxes)
    compressor = _bind_mesh(compressor, mesh, daxes)
    downlink = _bind_mesh(downlink, mesh, daxes)
    check_vma = _checks_vma(compressor, downlink)
    up = chn.ShardChannel(compressor, "uplink")
    down = chn.ShardChannel(downlink, "downlink")
    down_active = not down.is_identity()
    # partial participation needs each worker's lagging master view even
    # without a compressed downlink (non-syncers fall behind the master)
    carry_view = down_active or partial

    def _spec_leaves_for(tree):
        is_spec = lambda z: isinstance(z, P) or z is None
        if param_specs is None:
            return None
        flat = jax.tree_util.tree_leaves(param_specs, is_leaf=is_spec)
        n = len(jax.tree_util.tree_leaves(tree))
        if len(flat) != n:
            reps = max(1, n // len(flat))
            flat = flat * reps
        return flat

    def _z1mask(master):
        """Per-leaf ZeRO-1 shard axis (int; -1 = replicated)."""
        leaves, td = jax.tree_util.tree_flatten(master)
        specs = _spec_leaves_for(master) or [None] * len(leaves)
        mask = []
        for x, sp in zip(leaves, specs):
            ax = _zero1_axis(x.shape, sp, R) if zero1 else None
            mask.append(-1 if ax is None else ax)
        return jax.tree_util.tree_unflatten(td, mask)

    def _gather_master(master, z1):
        return jax.tree_util.tree_map(
            lambda x, m: _allgather_axis(x, daxes, m) if m >= 0 else x,
            master, z1)

    def _scatter_master(master, z1):
        return jax.tree_util.tree_map(
            lambda x, m: _shard_axis(x, daxes, m) if m >= 0 else x,
            master, z1)

    def _master_in_specs(z1):
        if not zero1:
            return P()
        return jax.tree_util.tree_map(
            lambda m: P(*([None] * m), tuple(daxes)) if m >= 0 else P(), z1)

    def _squeeze(tree):
        return jax.tree_util.tree_map(lambda x: x[0], tree)

    def _expand(tree):
        return jax.tree_util.tree_map(lambda x: x[None], tree)

    # ---- local phase (shared) ------------------------------------------
    # Each stage's outputs leave the manual region through ``_expand``
    # inside that stage's scope: XLA names a fusion after its root, and
    # the root is often that reshape.
    def _local(master, local, memory, inner, step, batch):
        """(half step, the inner state with its worker axis, loss)."""
        with jax.named_scope("qsparse.grad"):
            params = _squeeze(local)
            loss, grads = grad_fn(params, _squeeze(batch))
        with jax.named_scope("qsparse.optimizer"):
            lr = lr_schedule(step)
            updates, inner_new = inner_opt.update(grads, _squeeze(inner),
                                                  params, lr)
            half = apply_updates(params, updates)
            inner_new = _expand(inner_new)
        return half, inner_new, loss

    # ---- local step -----------------------------------------------------
    def local_body(master, local, memory, inner, step, batch, key):
        half, inner_new, loss = _local(master, local, memory, inner, step,
                                       batch)
        with jax.named_scope("qsparse.optimizer"):
            half = _expand(half)
        with jax.named_scope("qsparse.exchange"):
            loss = jax.lax.pmean(loss, daxes)
        return half, inner_new, loss

    # ---- aggregation rules (DESIGN.md §8) -------------------------------
    def _aggregate_psum(g, s_f):
        """Masked payload tree → the master's per-coordinate divisor.
        ``s_f`` is this worker's participation as f32 (1.0 when the
        step was built without masks).  mean_R keeps the historical
        ``pmean`` lowering verbatim."""
        if aggregate == "mean_R":
            return jax.tree_util.tree_map(
                lambda gg: jax.lax.pmean(gg, daxes), g)
        if aggregate == "mean_S":
            n_sync = (jnp.maximum(jax.lax.psum(s_f, daxes), 1.0)
                      if partial else jnp.float32(R))
            return jax.tree_util.tree_map(
                lambda gg: jax.lax.psum(gg, daxes) / n_sync, g)
        # support_weighted: per-coordinate survivor count over the
        # syncing workers' payloads (masked workers' g is exactly 0, so
        # they support nothing); zero-support coords have a zero
        # numerator too — the max(cnt, 1) guard leaves the master alone
        return jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, daxes) / jnp.maximum(
                jax.lax.psum((gg != 0).astype(jnp.float32), daxes), 1.0),
            g)

    # ---- sync step ------------------------------------------------------
    def make_sync_body(z1, with_down: bool = False):
      """Dense sync body.  With ``with_down`` (compressed downlink
      channel, DESIGN.md §5) the signature gains (view, down_mem): the
      uplink delta is taken against the worker's lagging *view*
      x_t^{(r)}, and after the master update the server compresses each
      worker's master delta against its error memory md^{(r)} — all
      shard-local threshold selection, sort- and collective-free.

      With ``partial`` (closure) the signature additionally gains a
      worker-sharded sync_mask and carries the view even without a
      downlink: masked-out workers transmit zeros (their payload is
      zeroed *before* the psum), keep their error memory and their
      half-step local iterate, and their view stays on the master copy
      they last received."""
      def sync_body(master, local, memory, inner, *rest):
        rest = list(rest)
        view = rest.pop(0) if carry_view else None
        down_mem = rest.pop(0) if with_down else None
        smask = rest.pop(0) if partial else None
        step, batch, key = rest
        half, inner_new, loss = _local(master, local, memory, inner, step,
                                       batch)
        # zero1 masters are sharded on axis 0 over the worker axes:
        # materialize the full master for the delta via all_gather
        with jax.named_scope("qsparse.exchange"):
            full_master = _gather_master(master, z1)
        with jax.named_scope("qsparse.compress"):
            mem = _squeeze(memory)
            ref = _squeeze(view) if carry_view else full_master
            delta = jax.tree_util.tree_map(
                lambda m, x, h: m + x.astype(jnp.float32)
                - h.astype(jnp.float32),
                mem, ref, half,
            )
            g, new_mem, wire_bits = up.apply(
                delta, param_specs, key=jax.random.fold_in(key, 1))
            if partial:
                s = smask[0]
                s_f = s.astype(jnp.float32)
                g = jax.tree_util.tree_map(
                    lambda gg: jnp.where(s, gg, jnp.zeros_like(gg)), g)
                new_mem = jax.tree_util.tree_map(
                    lambda old, nm: jnp.where(s, nm, old), mem, new_mem)
                wire_bits = jnp.where(s, wire_bits, 0.0)
            else:
                s, s_f = None, jnp.float32(1.0)
            mem_out = _expand(new_mem)
        with jax.named_scope("qsparse.exchange"):
            g_mean = _aggregate_psum(g, s_f)
            total_bits = jax.lax.psum(wire_bits, daxes)
            loss = jax.lax.pmean(loss, daxes)

        def picked(new, old):
            """Per-worker select: the new value only where s_r."""
            if not partial:
                return new
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(s, n.astype(o.dtype), o), new, old)

        with jax.named_scope("qsparse.master_update"):
            new_full_master = jax.tree_util.tree_map(
                lambda x, gg: (x.astype(jnp.float32) - gg).astype(x.dtype),
                full_master, g_mean,
            )
            new_master = _scatter_master(new_full_master, z1)
            if not with_down:
                new_local = picked(new_full_master, half)
                out = (
                    new_master,
                    _expand(new_local),   # exact broadcast (syncers only)
                    mem_out,
                    inner_new,
                )
                if carry_view:
                    out = out + (_expand(picked(new_full_master, ref)),)
                return out + (total_bits, loss)
        # downlink: error-compensated compression of the master delta
        with jax.named_scope("qsparse.downlink"):
            dm = _squeeze(down_mem)
            dacc = jax.tree_util.tree_map(
                lambda d, nm, vv: d + nm.astype(jnp.float32)
                - vv.astype(jnp.float32),
                dm, new_full_master, ref,
            )
            q, new_dm, dbits = down.apply(
                dacc, param_specs, key=jax.random.fold_in(key, 2))
            new_view = jax.tree_util.tree_map(
                lambda vv, qq: (vv.astype(jnp.float32) + qq).astype(vv.dtype),
                ref, q,
            )
            if partial:
                new_view = picked(new_view, ref)
                new_dm = jax.tree_util.tree_map(
                    lambda old, nm: jnp.where(s, nm, old), dm, new_dm)
                dbits = jnp.where(s, dbits, 0.0)
            new_local = picked(new_view, half)
            total_down = jax.lax.psum(dbits, daxes)
            return (
                new_master,
                _expand(new_local),  # x̂_{t+1} = x_{t+1} = view (syncers)
                mem_out,
                inner_new,
                _expand(new_view),
                _expand(new_dm),
                total_bits,
                total_down,
                loss,
            )
      return sync_body

    # ---- spec plumbing ---------------------------------------------------
    # shard_map in_specs/out_specs may only reference the *manual* axes;
    # 'model' sharding of the arrays is carried by XLA-auto untouched.
    # Master specs are built lazily per-leaf (zero1 only shards leaves
    # whose axis 0 divides by the worker count).
    worker_specs = P(daxes)
    batch_spec = P(daxes)

    def _shmap(body, master_specs, out_specs, extra_worker: int = 0):
        """``extra_worker`` counts additional worker-sharded operands
        threaded between the core state and (step, batch, key): the
        downlink channel state (view, down_memory) and/or the per-step
        sync mask of a partial-participation run."""
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                (master_specs, worker_specs, worker_specs, worker_specs)
                + (worker_specs,) * extra_worker
                + (P(), batch_spec, P())
            ),
            out_specs=out_specs,
            axis_names=manual,
            check_vma=check_vma,
        )

    def _bits_down_of(state):
        return (state.bits_down if state.bits_down is not None
                else jnp.zeros((), jnp.float32))

    # dense broadcast cost of one exact sync (per-receiver, Σ workers);
    # leaf sizes are static so this is a trace-time python float
    def _exact_down_bits(master):
        return jnp.float32(R * down.dense_bits(master))

    def local_step(state: DistQsparseState, batch, key):
        z1 = _z1mask(state.master)
        local_mapped = _shmap(local_body, _master_in_specs(z1),
                              (worker_specs, worker_specs, P()))
        half, inner_new, loss = local_mapped(
            state.master, state.local, state.memory, state.inner,
            state.step, batch, key,
        )
        return (
            DistQsparseState(
                master=state.master, local=half, memory=state.memory,
                inner=inner_new, step=state.step + 1, bits=state.bits,
                rounds=state.rounds, view=state.view,
                down_memory=state.down_memory,
                bits_down=state.bits_down,
            ),
            loss,
        )

    def _prep_mask(sync_mask):
        if sync_mask is None:
            raise ValueError(
                "this step was built with partial=True: pass the bool[R] "
                "sync_mask of the step (which workers sync now)")
        return jnp.asarray(sync_mask).reshape((R,)).astype(bool)

    def sync_step_dense(state: DistQsparseState, batch, key,
                        sync_mask=None):
        m = _prep_mask(sync_mask) if partial else None
        z1 = _z1mask(state.master)
        mspecs = _master_in_specs(z1)
        extra_in = ()
        if carry_view:
            extra_in += (state.view,)
        if down_active:
            extra_in += (state.down_memory,)
        if partial:
            extra_in += (m,)
        rounds_inc = jnp.any(m).astype(jnp.int32) if partial else 1
        if down_active:
            sync_mapped = _shmap(
                make_sync_body(z1, with_down=True), mspecs,
                (mspecs, worker_specs, worker_specs, worker_specs,
                 worker_specs, worker_specs, P(), P(), P()),
                extra_worker=len(extra_in))
            (master, local, memory, inner_new, view, down_mem, wire_bits,
             down_bits, loss) = sync_mapped(
                state.master, state.local, state.memory, state.inner,
                *extra_in, state.step, batch, key,
            )
            return (
                DistQsparseState(
                    master=master, local=local, memory=memory,
                    inner=inner_new, step=state.step + 1,
                    bits=state.bits + wire_bits,
                    rounds=state.rounds + rounds_inc, view=view,
                    down_memory=down_mem,
                    bits_down=_bits_down_of(state) + down_bits,
                ),
                loss,
            )
        out_specs = (mspecs, worker_specs, worker_specs, worker_specs)
        if carry_view:
            out_specs = out_specs + (worker_specs,)
        sync_mapped = _shmap(
            make_sync_body(z1), mspecs,
            out_specs + (P(), P()), extra_worker=len(extra_in))
        out = sync_mapped(
            state.master, state.local, state.memory, state.inner,
            *extra_in, state.step, batch, key,
        )
        if carry_view:
            master, local, memory, inner_new, view, wire_bits, loss = out
        else:
            master, local, memory, inner_new, wire_bits, loss = out
            view = state.view
        # exact broadcast cost: only the syncing workers receive x_{t+1}
        down_cost = (jnp.sum(m.astype(jnp.float32))
                     * jnp.float32(down.dense_bits(state.master))
                     if partial else _exact_down_bits(state.master))
        return (
            DistQsparseState(
                master=master, local=local, memory=memory, inner=inner_new,
                step=state.step + 1, bits=state.bits + wire_bits,
                rounds=state.rounds + rounds_inc, view=view,
                down_memory=state.down_memory,
                bits_down=_bits_down_of(state) + down_cost,
            ),
            loss,
        )

    # ---- sparse-allgather sync (§Perf beyond-paper aggregation) ---------
    # The manual region emits each worker's *compact* (idx, val) survivor
    # buffers with a leading worker axis — written directly by the
    # compact Pallas kernel (DESIGN.md §3.3), which also hands back the
    # fused error memory, so no densify/scatter runs inside the manual
    # region.  The dense mean is reconstructed in the auto region, so
    # the wire carries W*kcap entries per row instead of a dense-f32
    # ring all-reduce.  Sort-free end to end: the traced step contains
    # no lax.top_k.
    def _leaf_meta(master_tree, comp: Optional[ShardCompressor] = None):
        comp = compressor if comp is None else comp
        return comp.leaf_meta(master_tree, param_specs)

    def _compact_arrays(payloads):
        arrays = []
        for pl in payloads:
            if pl[0] == "dense":
                arrays.append(pl[1])
            else:
                _, idx, sel, _ax, _moved = pl
                arrays.append(idx)
                arrays.append(sel)
        return arrays

    def make_sparse_sync_body(z1):
      def sparse_sync_body(master, local, memory, inner, *rest):
        rest = list(rest)
        view = rest.pop(0) if carry_view else None
        smask = rest.pop(0) if partial else None
        step, batch, key = rest
        half, inner_new, loss = _local(master, local, memory, inner, step,
                                       batch)
        # with a compressed downlink (or a partial-participation run)
        # the uplink reference point is the worker's lagging view, not
        # the true master
        if carry_view:
            ref = _squeeze(view)
        else:
            with jax.named_scope("qsparse.exchange"):
                ref = _gather_master(master, z1)
        with jax.named_scope("qsparse.compress"):
            mem = _squeeze(memory)
            delta = jax.tree_util.tree_map(
                lambda m, x, h: m + x.astype(jnp.float32)
                - h.astype(jnp.float32),
                mem, ref, half,
            )
            payloads, _treedef, wire_bits, new_mem = compressor.compact(
                delta, param_specs, key=jax.random.fold_in(key, 1))
            if partial:
                # masked-out workers transmit nothing: zero their payload
                # values (sentinel-style — the auto-region scatter-add and
                # the support counts both see zeros), keep their memory
                s = smask[0]
                new_mem = jax.tree_util.tree_map(
                    lambda old, nm: jnp.where(s, nm, old), mem, new_mem)
                wire_bits = jnp.where(s, wire_bits, 0.0)
                arrays = []
                for pl in payloads:
                    if pl[0] == "dense":
                        arrays.append(
                            jnp.where(s, pl[1], jnp.zeros_like(pl[1])))
                    else:
                        _, idx, sel, _ax, _moved = pl
                        arrays.append(idx)
                        arrays.append(
                            jnp.where(s, sel, jnp.zeros_like(sel)))
            else:
                arrays = _compact_arrays(payloads)
            out = (_expand(new_mem), inner_new)
            wire = [a[None] for a in arrays]
        if partial:
            with jax.named_scope("qsparse.optimizer"):
                out = out + (_expand(half),)
        with jax.named_scope("qsparse.exchange"):
            total_bits = jax.lax.psum(wire_bits, daxes)
            loss = jax.lax.pmean(loss, daxes)
        return out + (wire, total_bits, loss)
      return sparse_sync_body

    def make_sparse_down_body():
      """Second manual region of the sparse downlink: the server-side
      error-compensated compression of each worker's master delta,
      emitted in the compact (idx, val) wire form (DESIGN.md §3.3) so
      the buffers leave via out_specs and the dense decode happens in
      the auto region — sort-free, collective-free (bar the scalar
      bits psum)."""
      def down_body(new_master, view, down_mem, *rest):
        rest = list(rest)
        smask = rest.pop(0) if partial else None
        (key,) = rest
        v = _squeeze(view)
        dm = _squeeze(down_mem)
        dacc = jax.tree_util.tree_map(
            lambda d, nm, vv: d + nm.astype(jnp.float32)
            - vv.astype(jnp.float32),
            dm, new_master, v,
        )
        payloads, _treedef, dbits, new_dm = down.compact(
            dacc, param_specs, key=jax.random.fold_in(key, 2))
        if partial:
            # dropped workers receive nothing: server memory and bits
            # freeze; their q is discarded in the auto-region select
            s = smask[0]
            new_dm = jax.tree_util.tree_map(
                lambda old, nm: jnp.where(s, nm, old), dm, new_dm)
            dbits = jnp.where(s, dbits, 0.0)
        arrays = _compact_arrays(payloads)
        total_down = jax.lax.psum(dbits, daxes)
        return (_expand(new_dm), [a[None] for a in arrays], total_down)
      return down_body

    def _decode_mean(arrays, meta, master, z1, m):
        """The workers' mean payload, per leaf, from the gathered
        sparse-wire buffers."""
        n_sync = (jnp.maximum(jnp.sum(m.astype(jnp.float32)), 1.0)
                  if partial else None)
        # auto-region combine: dense mean per leaf, constrained to the
        # master's own sharding so the dense tree is never replicated
        # (zero1 leaves: sharded over the worker axes; each chip
        # reconstructs only its master shard from the gathered compacts).
        it = iter(arrays)
        master_leaves, mtd = jax.tree_util.tree_flatten(master)
        z1_leaves = jax.tree_util.tree_leaves(z1)
        means = []
        for (kind, ax, moved), mleaf, z1m in zip(meta, master_leaves,
                                                 z1_leaves):
            if kind == "dense":
                arr = next(it)
                if aggregate == "mean_R":
                    means.append(jnp.mean(arr, axis=0))
                elif aggregate == "mean_S":
                    d = n_sync if partial else jnp.float32(arr.shape[0])
                    means.append(jnp.sum(arr, axis=0) / d)
                else:  # support_weighted
                    cnt = jnp.sum((arr != 0).astype(jnp.float32), axis=0)
                    means.append(jnp.sum(arr, axis=0)
                                 / jnp.maximum(cnt, 1.0))
                continue
            idx_all = next(it)      # [W, ..., kcap]
            sel_all = next(it)
            W_ = idx_all.shape[0]
            # all W workers' buffers for a row decode in one scatter-add
            # (row-local indices are worker-independent; sentinels drop)
            from repro.kernels.dispatch import decode_rows
            ii = jnp.moveaxis(idx_all, 0, -2).reshape(
                (-1, W_ * idx_all.shape[-1]))
            ss = jnp.moveaxis(sel_all, 0, -2).reshape(
                (-1, W_ * sel_all.shape[-1]))
            dense = decode_rows(ii, ss, moved[-1])
            dense = jnp.moveaxis(dense.reshape(moved), -1, ax)
            z1spec = NamedSharding(mesh, P(*([None] * z1m), tuple(daxes))) \
                if z1m >= 0 else None
            if z1spec is not None:
                dense = jax.lax.with_sharding_constraint(dense, z1spec)
            if aggregate == "mean_R":
                means.append(dense / W_)
            elif aggregate == "mean_S":
                means.append(dense / (n_sync if partial
                                      else jnp.float32(W_)))
            else:  # support_weighted: survivor count per coordinate
                cnt = decode_rows(ii, (ss != 0).astype(jnp.float32),
                                  moved[-1])
                cnt = jnp.moveaxis(cnt.reshape(moved), -1, ax)
                if z1spec is not None:
                    cnt = jax.lax.with_sharding_constraint(cnt, z1spec)
                means.append(dense / jnp.maximum(cnt, 1.0))
        return jax.tree_util.tree_unflatten(mtd, means)

    def sync_step_sparse(state: DistQsparseState, batch, key,
                         sync_mask=None):
        m = _prep_mask(sync_mask) if partial else None
        z1 = _z1mask(state.master)
        meta = _leaf_meta(state.master)
        n_arrays = sum(1 if mt[0] == "dense" else 2 for mt in meta)
        extra_in, extra_specs = (), ()
        if carry_view:
            extra_in += (state.view,)
            extra_specs += (worker_specs,)
        if partial:
            extra_in += (m,)
            extra_specs += (worker_specs,)
        half_specs = (worker_specs,) if partial else ()
        mapped = jax.shard_map(
            make_sparse_sync_body(z1), mesh=mesh,
            in_specs=(_master_in_specs(z1), worker_specs, worker_specs,
                      worker_specs) + extra_specs + (P(), batch_spec, P()),
            out_specs=(worker_specs, worker_specs) + half_specs
            + ([P(tuple(daxes))] * n_arrays, P(), P()),
            axis_names=manual, check_vma=check_vma,
        )
        out = mapped(
            state.master, state.local, state.memory, state.inner,
            *extra_in, state.step, batch, key)
        if partial:
            memory, inner_new, half_all, arrays, wire_bits, loss = out
        else:
            memory, inner_new, arrays, wire_bits, loss = out
            half_all = None
        with jax.named_scope("qsparse.decode"):
            g_mean = _decode_mean(arrays, meta, state.master, z1, m)
        rounds_inc = jnp.any(m).astype(jnp.int32) if partial else 1

        def _select(old_all):
            """Broadcast the new master to the (syncing) workers; the
            dropped workers keep ``old_all`` (their half-step iterate
            or stale view)."""
            def leaf(x, o):
                b = jnp.broadcast_to(x[None], o.shape).astype(o.dtype)
                if partial:
                    b = jnp.where(
                        m.reshape((-1,) + (1,) * (o.ndim - 1)), b, o)
                return jax.lax.with_sharding_constraint(
                    b, NamedSharding(mesh, P(tuple(daxes))))
            return jax.tree_util.tree_map(leaf, new_master, old_all)

        # zero1 masters keep their global shape (only the sharding
        # differs), so the update is uniform across both layouts.
        with jax.named_scope("qsparse.master_update"):
            new_master = jax.tree_util.tree_map(
                lambda x, gg: (x.astype(jnp.float32) - gg).astype(x.dtype),
                state.master, g_mean)
            if not down_active:
                new_local = _select(half_all if partial else state.local)
                new_view = _select(state.view) if carry_view else state.view
        if down_active:
            with jax.named_scope("qsparse.downlink"):
                new_local, view, down_mem, down_bits = _sparse_downlink(
                    state, new_master, key, m, half_all)
            return (
                DistQsparseState(
                    master=new_master, local=new_local, memory=memory,
                    inner=inner_new, step=state.step + 1,
                    bits=state.bits + wire_bits,
                    rounds=state.rounds + rounds_inc,
                    view=view, down_memory=down_mem,
                    bits_down=_bits_down_of(state) + down_bits,
                ),
                loss,
            )
        down_cost = (jnp.sum(m.astype(jnp.float32))
                     * jnp.float32(down.dense_bits(state.master))
                     if partial else _exact_down_bits(state.master))
        return (
            DistQsparseState(
                master=new_master, local=new_local, memory=memory,
                inner=inner_new, step=state.step + 1,
                bits=state.bits + wire_bits,
                rounds=state.rounds + rounds_inc,
                view=new_view, down_memory=state.down_memory,
                bits_down=_bits_down_of(state) + down_cost,
            ),
            loss,
        )

    def _sparse_downlink(state, new_master, key, smask=None,
                         half_all=None):
        """Sparse-path downlink: a second manual region emits each
        worker's compact (idx, val) downlink buffers + updated server
        memory; the per-worker dense decode (scatter-add, sentinel
        slots drop) runs in the auto region, exactly like the uplink
        combine — no mean: each worker applies only its own q.  With
        ``partial`` the dropped workers (smask False) keep their view,
        server memory and half-step local iterate ``half_all``."""
        dmeta = _leaf_meta(state.master, downlink)
        n_down = sum(1 if mt[0] == "dense" else 2 for mt in dmeta)
        master_in = new_master
        if zero1:
            # replicate the (z1-sharded) new master in the auto region:
            # the down body takes the full master (in_specs P())
            master_in = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P())), new_master)
        mask_in = (smask,) if partial else ()
        mask_specs = (worker_specs,) if partial else ()
        down_mapped = jax.shard_map(
            make_sparse_down_body(), mesh=mesh,
            in_specs=(P(), worker_specs, worker_specs)
            + mask_specs + (P(),),
            out_specs=(worker_specs, [P(tuple(daxes))] * n_down, P()),
            axis_names=manual, check_vma=check_vma,
        )
        down_mem, darrays, down_bits = down_mapped(
            master_in, state.view, state.down_memory, *mask_in, key)
        it = iter(darrays)
        view_leaves, vtd = jax.tree_util.tree_flatten(state.view)
        new_view_leaves = []
        from repro.kernels.dispatch import decode_rows
        for (kind, ax, moved), vleaf in zip(dmeta, view_leaves):
            if kind == "dense":
                q = next(it)                    # [W, ...] exact payload
            else:
                idx_all = next(it)              # [W, ..., kcap]
                sel_all = next(it)
                W_ = idx_all.shape[0]
                kcap = idx_all.shape[-1]
                dense = decode_rows(idx_all.reshape(-1, kcap),
                                    sel_all.reshape(-1, kcap), moved[-1])
                dense = dense.reshape((W_,) + tuple(moved))
                q = jnp.moveaxis(dense, -1, ax + 1)
            new_view_leaves.append(
                (vleaf.astype(jnp.float32) + q).astype(vleaf.dtype))
        new_view = jax.tree_util.tree_unflatten(vtd, new_view_leaves)
        if partial:
            mb = lambda o: smask.reshape((-1,) + (1,) * (o.ndim - 1))
            new_view = jax.tree_util.tree_map(
                lambda nv, v: jnp.where(mb(v), nv, v),
                new_view, state.view)
        new_view = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(tuple(daxes)))), new_view)
        if partial:
            new_local = jax.tree_util.tree_map(
                lambda nv, h: jax.lax.with_sharding_constraint(
                    jnp.where(mb(h), nv.astype(h.dtype), h),
                    NamedSharding(mesh, P(tuple(daxes)))),
                new_view, half_all)
            return new_local, new_view, down_mem, down_bits
        return new_view, new_view, down_mem, down_bits

    sync_step = (sync_step_sparse if wire == "sparse_allgather"
                 else sync_step_dense)

    # ---- init ------------------------------------------------------------
    def init_fn(params):
        """``params`` enter fully replicated over the worker axes."""
        z1 = _z1mask(params)

        def body(p):
            local = _expand(p)
            memory = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), local
            )
            inner = _expand(inner_opt.init(p))
            master = _scatter_master(p, z1)
            out = [master, local, memory, inner]
            if carry_view:
                # every worker's initial view is the initial master
                out.append(local)
            if down_active:
                # server-side downlink error memory starts at zero
                out.append(down.init_memory(local))
            return tuple(out)

        out_specs = (_master_in_specs(z1), worker_specs, worker_specs,
                     worker_specs)
        out_specs += (worker_specs,) * (int(carry_view) + int(down_active))
        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=out_specs,
            axis_names=manual, check_vma=check_vma,
        )
        # eager shard_map with auto (non-manual) axes is unimplemented;
        # under jit it lowers
        out = jax.jit(mapped)(params)
        master, local, memory, inner = out[:4]
        view = out[4] if carry_view else None
        down_mem = out[4 + int(carry_view)] if down_active else None
        # counters committed to the mesh like the ones a step returns,
        # so the second call of a jitted step does not compile again
        zero = lambda dt: jax.device_put(  # noqa: E731
            jnp.zeros((), dt), NamedSharding(mesh, P()))
        return DistQsparseState(
            master=master, local=local, memory=memory, inner=inner,
            step=zero(jnp.int32), bits=zero(jnp.float32),
            rounds=zero(jnp.int32), view=view, down_memory=down_mem,
            bits_down=zero(jnp.float32),
        )

    return init_fn, local_step, sync_step


def _make_round_core(local_step, sync_step):
    """One sync round as a traced program: lax.scan of the shard_mapped
    local step over the head, the sync step once at the tail, key split
    in-program with the host loop's sequence.  Shared by the fused
    round program and the windowed multi-round program."""
    def round_core(state, batch_block, key, *tail_mask):
        def body(carry, batch):
            state, key = carry
            key, sub = jax.random.split(key)
            state, loss = local_step(state, batch, sub)
            return (state, key), loss

        head = jax.tree_util.tree_map(lambda x: x[:-1], batch_block)
        tail = jax.tree_util.tree_map(lambda x: x[-1], batch_block)
        (state, key), head_losses = jax.lax.scan(
            body, (state, key), head)
        key, sub = jax.random.split(key)
        state, tail_loss = sync_step(state, tail, sub, *tail_mask)
        return (state, jnp.concatenate([head_losses, tail_loss[None]]),
                key)

    return round_core


def make_dist_round(
    grad_fn: Callable,
    inner_opt: GradientTransform,
    compressor: ShardCompressor,
    lr_schedule: Callable,
    mesh,
    data_axes: Sequence[str] = ("data",),
    param_specs=None,
    zero1: bool = False,
    aggregate: str = "mean_R",
    downlink: Optional[ShardCompressor] = None,
    wire: str = "dense_psum",
    partial: bool = False,
):
    """Round-program runtime for the mesh engine (DESIGN.md §7).

    Returns ``(init_fn, round_fn)``.  ``round_fn(state,
    batch_block, key) -> (state, losses[L], key)`` executes one sync
    round — L−1 local steps then the sync step at the tail, where L is
    the block's leading dim (the host schedule guarantees the tail is
    the round's sync step; use L=1 blocks for back-to-back syncs).

    With ``partial=True`` (scenario runs, core/scenarios.py) the round
    signature gains the tail's per-worker mask: ``round_fn(state,
    batch_block, tail_mask, key)`` with ``tail_mask`` bool[R] — which
    workers contribute to the round's sync.  ``aggregate`` names the
    master's division rule (mean_R | mean_S | support_weighted) and
    ``wire`` the transport (dense_psum | sparse_allgather); legacy
    callers passing a wire format as ``aggregate=`` are shimmed with a
    one-time warning (see make_dist_steps).

    The whole round is ONE donated jitted program: ``lax.scan`` over
    the shard_mapped local step with the batch block as xs, the
    shard_mapped sync step once at the tail, per-step losses
    accumulated on device and the PRNG key split in-program with the
    host loop's sequence — bit-for-bit the per-step trajectories.
    """
    init_fn, local_step, sync_step = make_dist_steps(
        grad_fn, inner_opt, compressor, lr_schedule, mesh, data_axes,
        param_specs, zero1=zero1, aggregate=aggregate, downlink=downlink,
        wire=wire, partial=partial)
    round_core = _make_round_core(local_step, sync_step)

    if partial:
        def round_program(state, batch_block, tail_mask, key):
            return round_core(state, batch_block, key, tail_mask)
    else:
        round_program = round_core

    from repro.core.engine import donated_jit
    return init_fn, donated_jit(round_program)


def make_dist_multiround(
    grad_fn: Callable,
    inner_opt: GradientTransform,
    compressor: ShardCompressor,
    lr_schedule: Callable,
    mesh,
    data_axes: Sequence[str] = ("data",),
    param_specs=None,
    zero1: bool = False,
    aggregate: str = "mean_R",
    downlink: Optional[ShardCompressor] = None,
    wire: str = "dense_psum",
    partial: bool = False,
):
    """Windowed round program for the mesh engine — the overlapped
    driver's compiled unit (DESIGN.md §10, the mesh twin of
    ``engine.make_multiround``).

    Returns ``(init_fn, multiround_fn)``.  ``multiround_fn``
    takes ``(state, blocks, key)`` — or ``(state, blocks, tail_masks,
    key)`` with ``partial=True`` — where ``blocks`` stacks W
    equal-length round blocks ([W, L, ...] leaves) and ``tail_masks``
    is bool[W, R]; it returns ``(state, losses [W, L], key)``.  The W
    rounds execute as ONE donated program: an outer ``lax.scan`` whose
    body is exactly the fused round core, so round w+1's scanned local
    phase sits in the device queue while round w's sync collective
    (psum / allgather) completes — the collective pipelines against the
    next round's compute instead of serializing the dispatch chain.

    Bit-for-bit contract: the scan body is the same round core the
    serialized ``make_dist_round`` program jits, threading the same key
    stream, so states, losses and both wire ledgers match the per-round
    driver exactly.
    """
    init_fn, local_step, sync_step = make_dist_steps(
        grad_fn, inner_opt, compressor, lr_schedule, mesh, data_axes,
        param_specs, zero1=zero1, aggregate=aggregate, downlink=downlink,
        wire=wire, partial=partial)
    from repro.core.engine import donated_jit
    round_core = _make_round_core(local_step, sync_step)

    def multi_core(state, blocks, key, *tail_masks):
        def body(carry, xs):
            st, kk = carry
            if tail_masks:
                block, mask = xs
                st, ls, kk = round_core(st, block, kk, mask)
            else:
                st, ls, kk = round_core(st, xs, kk)
            return (st, kk), ls

        xs = (blocks, tail_masks[0]) if tail_masks else blocks
        (state, key), losses = jax.lax.scan(body, (state, key), xs)
        return state, losses, key

    if partial:
        def multiround(state, blocks, tail_masks, key):
            return multi_core(state, blocks, key, tail_masks)
    else:
        multiround = multi_core
    return init_fn, donated_jit(multiround)


# ---------------------------------------------------------------------------
# staleness-first fault runtime (DESIGN.md §9)
# ---------------------------------------------------------------------------


def make_dist_fault_steps(
    grad_fn: Callable,
    inner_opt: GradientTransform,
    compressor: ShardCompressor,
    lr_schedule: Callable,
    mesh,
    data_axes: Sequence[str] = ("data",),
    param_specs=None,
    *,
    queue_depth: int,
    aggregate: str = "mean_R",
    wire: str = "dense_psum",
    staleness_weight: str = "uniform",
    downlink: Optional[ShardCompressor] = None,
    zero1: bool = False,
):
    """Mesh-engine counterpart of ``engine.make_fault_step``: the
    *executed* staleness regime on both transports.  A payload computed
    at step t (uplink error memory updated, wire bits charged *then*)
    is enqueued into a bounded per-worker in-flight buffer and applied
    to the master at t+τ; workers crash (state frozen), recover
    (re-initialized from the current master, error memory lost), and
    payloads drop in flight per the step's ``engine.FaultRow``.

    Returns ``(init_fn, fault_local_step, fault_sync_step)``; both
    steps take ``(state, batch, row, key)``.  The host drives the
    dispatch from the deterministic fault tables
    (``scenarios.fault_replay``): event steps — any scheduled sync row
    or any arrival — go through ``fault_sync_step``, the rest through
    ``fault_local_step`` (recover + alive-masked local phase only).

    Wire semantics:

    * ``dense_psum`` — the queue holds each worker's *decompressed*
      payload per shard ([R, depth, ...] master-shaped buffers); the
      arriving slots are summed per worker inside the manual region and
      the cross-worker reduce is one psum, exactly the non-fault body's
      pattern.
    * ``sparse_allgather`` — delayed shards ride the existing compact
      wire format: the queue holds the (idx, val) survivor buffers
      themselves ([R, depth, ..., kcap]); at arrival the masked vals of
      *all* queued buffers decode in the auto region via the same
      scatter-add combine as the non-fault path (sentinel and zeroed
      slots contribute nothing).

    Both wires produce the same trajectories (states allclose, both
    bits ledgers exact — the compact bits counting is the dense
    channel's).  ``zero1`` and a compressed ``downlink`` are not
    supported under faults on the mesh engine (the single-host engine
    carries the compressed-downlink fault path); pass ``downlink`` only
    as None/identity.
    """
    from repro.core.scenarios import (validate_aggregate,
                                      validate_staleness_weight)
    validate_aggregate(aggregate)
    validate_staleness_weight(staleness_weight)
    if wire not in ("dense_psum", "sparse_allgather"):
        raise ValueError(f"unknown wire {wire!r}; expected 'dense_psum' "
                         f"| 'sparse_allgather'")
    if queue_depth < 1:
        raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
    if zero1:
        raise ValueError(
            "zero1 master sharding is not supported under faults: the "
            "recover phase re-initializes workers from the full master "
            "inside the manual region (gather-free); run faults with "
            "zero1=False")
    if downlink is not None and not chn.ShardChannel(
            downlink, "downlink").is_identity():
        raise ValueError(
            "a compressed downlink is not supported under faults on the "
            "mesh engine; use the single-host engine "
            "(engine.make_fault_step) for compressed-downlink fault "
            "studies, or downlink=None here")
    daxes = tuple(data_axes)
    R = worker_count(mesh, daxes)
    manual = set(daxes)
    compressor = _bind_mesh(compressor, mesh, daxes)
    check_vma = _checks_vma(compressor)
    up = chn.ShardChannel(compressor, "uplink")
    down = chn.ShardChannel(None, "downlink")
    Dq = int(queue_depth)
    damped = staleness_weight == "damped"
    worker_specs = P(daxes)
    batch_spec = P(daxes)

    def _squeeze(tree):
        return jax.tree_util.tree_map(lambda x: x[0], tree)

    def _expand(tree):
        return jax.tree_util.tree_map(lambda x: x[None], tree)

    def _wsel(flag, new, old):
        """Scalar-flag select over same-structure trees (in-body, one
        worker): the new value only where ``flag``."""
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(flag, n.astype(o.dtype), o), new, old)

    def _row_arrays(row):
        """FaultRow → five [R] device arrays (worker-shardable)."""
        as_r = lambda x, dt: jnp.asarray(x, dt).reshape((R,))  # noqa: E731
        return (as_r(row.sync, bool), as_r(row.delay, jnp.int32),
                as_r(row.alive, bool), as_r(row.drop, bool),
                as_r(row.recover, bool))

    def _check_queue(state):
        if state.inflight is None or state.arrive_at is None:
            raise ValueError(
                "fault steps need the in-flight queue: build the state "
                "with this factory's init_fn")

    # ---- shared in-body phases ------------------------------------------
    def _recover_and_local(master, local, memory, inner, view,
                           alive, recover, step, batch):
        """Recover phase + alive-masked local phase for one worker
        (squeezed trees).  Returns (half, memory, inner, view, loss) —
        the crashed workers' iterate/inner stay frozen, recovered
        workers restart from the master with fresh memory/inner."""
        l0, v0 = _squeeze(local), _squeeze(view)
        m0, i0 = _squeeze(memory), _squeeze(inner)
        fresh = jax.tree_util.tree_map(
            lambda x, l: x.astype(l.dtype), master, l0)
        l0 = _wsel(recover, fresh, l0)
        v0 = _wsel(recover, jax.tree_util.tree_map(
            lambda x, v: x.astype(v.dtype), master, v0), v0)
        m0 = _wsel(recover, jax.tree_util.tree_map(jnp.zeros_like, m0), m0)
        i0 = _wsel(recover, inner_opt.init(fresh), i0)
        loss, grads = grad_fn(l0, _squeeze(batch))
        updates, i1 = inner_opt.update(grads, i0, l0, lr_schedule(step))
        half = apply_updates(l0, updates)
        half = _wsel(alive, half, l0)
        i1 = _wsel(alive, i1, i0)
        return half, m0, i1, v0, loss

    def _uplink_payload(m0, v0, half, compute, key, compact: bool):
        """Compute-time error feedback, masked to the computing workers
        (scheduled sync AND alive): memory and bits advance *now*, the
        payload is handed to the queue.  Dense form returns the
        decompressed g tree; compact form the wire arrays."""
        delta = jax.tree_util.tree_map(
            lambda m, x, h: m + x.astype(jnp.float32)
            - h.astype(jnp.float32),
            m0, v0, half,
        )
        sub = jax.random.fold_in(key, 1)
        if compact:
            payloads, _td, wire_bits, new_mem = compressor.compact(
                delta, param_specs, key=sub)
        else:
            g, new_mem, wire_bits = up.apply(delta, param_specs, key=sub)
        new_mem = jax.tree_util.tree_map(
            lambda old, nm: jnp.where(compute, nm, old), m0, new_mem)
        wire_bits = jnp.where(compute, wire_bits, 0.0)
        if compact:
            arrays = []
            for pl in payloads:
                if pl[0] == "dense":
                    arrays.append(jnp.where(compute, pl[1],
                                            jnp.zeros_like(pl[1])))
                else:
                    _, idx, sel, _ax, _moved = pl
                    arrays.append(idx)
                    arrays.append(jnp.where(compute, sel,
                                            jnp.zeros_like(sel)))
            return arrays, new_mem, wire_bits
        g = jax.tree_util.tree_map(
            lambda gg: jnp.where(compute, gg, jnp.zeros_like(gg)), g)
        return g, new_mem, wire_bits

    # ---- local fault step (no event this step) --------------------------
    def local_fault_body(master, local, memory, inner, view,
                         sync, delay, alive, drop, recover,
                         step, batch, key):
        half, m0, i1, v0, loss = _recover_and_local(
            master, local, memory, inner, view, alive[0], recover[0],
            step, batch)
        loss = jax.lax.pmean(loss, daxes)
        return (_expand(half), _expand(m0), _expand(i1), _expand(v0),
                loss)

    def fault_local_step(state: DistQsparseState, batch, row, key):
        _check_queue(state)
        rows = _row_arrays(row)
        mapped = jax.shard_map(
            local_fault_body, mesh=mesh,
            in_specs=(P(), worker_specs, worker_specs, worker_specs,
                      worker_specs) + (worker_specs,) * 5
            + (P(), batch_spec, P()),
            out_specs=(worker_specs,) * 4 + (P(),),
            axis_names=manual, check_vma=check_vma,
        )
        local, memory, inner, view, loss = mapped(
            state.master, state.local, state.memory, state.inner,
            state.view, *rows, state.step, batch, key)
        return state._replace(local=local, memory=memory, inner=inner,
                              view=view, step=state.step + 1), loss

    # ---- dense wire: queue + arrivals inside the manual region ----------
    def dense_fault_body(master, local, memory, inner, view,
                         q, arrive, tau,
                         sync, delay, alive, drop, recover,
                         step, batch, key):
        alv, rec = alive[0], recover[0]
        half, m0, i1, v0, loss = _recover_and_local(
            master, local, memory, inner, view, alv, rec, step, batch)
        compute = sync[0] & alv
        g, new_mem, wire_bits = _uplink_payload(
            m0, v0, half, compute, key, compact=False)
        # enqueue: slot t % depth, arrival at t + τ (dropped payloads
        # were charged and compensated but never travel)
        slot = jnp.mod(step, Dq)
        keep = compute & ~drop[0]
        qs = _squeeze(q)                    # [Dq, ...] this worker
        arr_q, tau_q = arrive[0], tau[0]    # [Dq]
        qs = jax.tree_util.tree_map(
            lambda qq, gg: qq.at[slot].set(jnp.where(keep, gg, qq[slot])),
            qs, g)
        arr_q = arr_q.at[slot].set(
            jnp.where(keep, step + delay[0], arr_q[slot]))
        tau_q = tau_q.at[slot].set(jnp.where(keep, delay[0], tau_q[slot]))
        # apply: every in-flight payload landing this step
        landing = arr_q == step             # [Dq]

        def pay_of(qq):
            shape = (Dq,) + (1,) * (qq.ndim - 1)
            p = jnp.where(landing.reshape(shape), qq, jnp.zeros_like(qq))
            if damped:
                w = 1.0 / (1.0 + tau_q.astype(jnp.float32))
                p = p * w.reshape(shape)
            return p

        pays = jax.tree_util.tree_map(pay_of, qs)
        pay_sum = jax.tree_util.tree_map(
            lambda p: jnp.sum(p, axis=0), pays)
        if aggregate == "mean_R":
            g_agg = jax.tree_util.tree_map(
                lambda p: jax.lax.psum(p, daxes) / R, pay_sum)
        elif aggregate == "mean_S":
            n_arr = jnp.maximum(jax.lax.psum(
                jnp.sum(landing.astype(jnp.float32)), daxes), 1.0)
            g_agg = jax.tree_util.tree_map(
                lambda p: jax.lax.psum(p, daxes) / n_arr, pay_sum)
        else:  # support_weighted: arriving per-coordinate support
            g_agg = jax.tree_util.tree_map(
                lambda p, c: jax.lax.psum(jnp.sum(p, axis=0), daxes)
                / jnp.maximum(jax.lax.psum(jnp.sum(
                    (c != 0).astype(jnp.float32), axis=0), daxes), 1.0),
                pays, pays)
        new_master = jax.tree_util.tree_map(
            lambda x, gg: (x.astype(jnp.float32) - gg).astype(x.dtype),
            master, g_agg)
        # dequeue applied slots
        qs = jax.tree_util.tree_map(
            lambda qq: jnp.where(
                landing.reshape((Dq,) + (1,) * (qq.ndim - 1)),
                jnp.zeros_like(qq), qq),
            qs)
        arr_q = jnp.where(landing, -1, arr_q)
        tau_q = jnp.where(landing, 0, tau_q)
        # broadcast to workers whose payload landed (and are alive)
        arr_any = jnp.any(landing)
        b = arr_any & alv
        new_local = _wsel(b, new_master, half)
        new_view = _wsel(b, new_master, v0)
        total_bits = jax.lax.psum(wire_bits, daxes)
        loss = jax.lax.pmean(loss, daxes)
        return (new_master, _expand(new_local), _expand(new_mem),
                _expand(i1), _expand(new_view), _expand(qs),
                arr_q[None], tau_q[None], arr_any[None], total_bits,
                loss)

    def fault_sync_step_dense(state: DistQsparseState, batch, row, key):
        _check_queue(state)
        rows = _row_arrays(row)
        mapped = jax.shard_map(
            dense_fault_body, mesh=mesh,
            in_specs=(P(), worker_specs, worker_specs, worker_specs,
                      worker_specs, worker_specs, worker_specs,
                      worker_specs) + (worker_specs,) * 5
            + (P(), batch_spec, P()),
            out_specs=(P(),) + (worker_specs,) * 8 + (P(), P()),
            axis_names=manual, check_vma=check_vma,
        )
        (master, local, memory, inner, view, q, arrive, tau, arr_any,
         wire_bits, loss) = mapped(
            state.master, state.local, state.memory, state.inner,
            state.view, state.inflight, state.arrive_at,
            state.inflight_tau, *rows, state.step, batch, key)
        alive_r = rows[2]
        n_recv = jnp.sum((arr_any & alive_r).astype(jnp.float32))
        down_cost = n_recv * jnp.float32(down.dense_bits(state.master))
        return state._replace(
            master=master, local=local, memory=memory, inner=inner,
            view=view, step=state.step + 1,
            bits=state.bits + wire_bits,
            rounds=state.rounds + jnp.any(arr_any).astype(jnp.int32),
            bits_down=state.bits_down + down_cost,
            inflight=q, arrive_at=arrive, inflight_tau=tau,
        ), loss

    # ---- sparse wire: compact buffers queue in the auto region ----------
    def sparse_fault_body(master, local, memory, inner, view,
                          sync, delay, alive, drop, recover,
                          step, batch, key):
        alv, rec = alive[0], recover[0]
        half, m0, i1, v0, loss = _recover_and_local(
            master, local, memory, inner, view, alv, rec, step, batch)
        compute = sync[0] & alv
        arrays, new_mem, wire_bits = _uplink_payload(
            m0, v0, half, compute, key, compact=True)
        total_bits = jax.lax.psum(wire_bits, daxes)
        loss = jax.lax.pmean(loss, daxes)
        return (_expand(half), _expand(new_mem), _expand(i1),
                _expand(v0), [a[None] for a in arrays], total_bits, loss)

    def fault_sync_step_sparse(state: DistQsparseState, batch, row, key):
        _check_queue(state)
        rows = _row_arrays(row)
        sync_r, delay_r, alive_r, drop_r, _rec = rows
        meta = compressor.leaf_meta(state.master, param_specs)
        n_arrays = sum(1 if mt[0] == "dense" else 2 for mt in meta)
        mapped = jax.shard_map(
            sparse_fault_body, mesh=mesh,
            in_specs=(P(), worker_specs, worker_specs, worker_specs,
                      worker_specs) + (worker_specs,) * 5
            + (P(), batch_spec, P()),
            out_specs=(worker_specs,) * 4
            + ([P(tuple(daxes))] * n_arrays, P(), P()),
            axis_names=manual, check_vma=check_vma,
        )
        half_all, memory, inner, view, arrays, wire_bits, loss = mapped(
            state.master, state.local, state.memory, state.inner,
            state.view, *rows, state.step, batch, key)
        # ---- enqueue into the compact queue (auto region) --------------
        compute = sync_r & alive_r
        keep = compute & ~drop_r
        slot = jnp.mod(state.step, Dq)

        def put(buf, payload):
            kmask = keep.reshape((R,) + (1,) * (payload.ndim - 1))
            return buf.at[:, slot].set(
                jnp.where(kmask, payload, buf[:, slot]))

        bufs = list(state.inflight)
        it = iter(arrays)
        new_bufs = []
        bi = 0
        for kind, _ax, _moved in meta:
            if kind == "dense":
                new_bufs.append(put(bufs[bi], next(it)))
                bi += 1
            else:
                new_bufs.append(put(bufs[bi], next(it)))      # idx
                new_bufs.append(put(bufs[bi + 1], next(it)))  # val
                bi += 2
        arrive = state.arrive_at.at[:, slot].set(
            jnp.where(keep, state.step + delay_r,
                      state.arrive_at[:, slot]))
        tau = state.inflight_tau.at[:, slot].set(
            jnp.where(keep, delay_r, state.inflight_tau[:, slot]))
        # ---- apply: decode every landing buffer, scatter-add combine ---
        landing = arrive == state.step                      # [R, Dq]
        w = (1.0 / (1.0 + tau.astype(jnp.float32))) if damped else None
        n_arr = jnp.maximum(jnp.sum(landing.astype(jnp.float32)), 1.0)
        from repro.kernels.dispatch import decode_rows
        master_leaves, mtd = jax.tree_util.tree_flatten(state.master)
        it = iter(new_bufs)
        means = []
        for (kind, ax, moved), mleaf in zip(meta, master_leaves):
            if kind == "dense":
                buf = next(it)                              # [R, Dq, ...]
                lm = landing.reshape((R, Dq) + (1,) * (buf.ndim - 2))
                p = jnp.where(lm, buf, jnp.zeros_like(buf))
                if damped:
                    p = p * w.reshape((R, Dq) + (1,) * (buf.ndim - 2))
                s = jnp.sum(p, axis=(0, 1))
                if aggregate == "mean_R":
                    means.append(s / R)
                elif aggregate == "mean_S":
                    means.append(s / n_arr)
                else:
                    cnt = jnp.sum((p != 0).astype(jnp.float32),
                                  axis=(0, 1))
                    means.append(s / jnp.maximum(cnt, 1.0))
                continue
            idx_buf = next(it)                  # [R, Dq, ..., kcap]
            val_buf = next(it)
            lm = landing.reshape((R, Dq) + (1,) * (val_buf.ndim - 2))
            vals = jnp.where(lm, val_buf, jnp.zeros_like(val_buf))
            if damped:
                vals = vals * w.reshape((R, Dq) + (1,) * (val_buf.ndim - 2))
            kcap = idx_buf.shape[-1]
            ii = idx_buf.reshape(-1, kcap)
            ss = vals.reshape(-1, kcap)
            dense = decode_rows(ii, ss, moved[-1])
            dense = dense.reshape((R, Dq) + tuple(moved))
            s = jnp.moveaxis(jnp.sum(dense, axis=(0, 1)), -1, ax)
            if aggregate == "mean_R":
                means.append(s / R)
            elif aggregate == "mean_S":
                means.append(s / n_arr)
            else:
                cntd = decode_rows(ii, (ss != 0).astype(jnp.float32),
                                   moved[-1])
                cnt = jnp.moveaxis(
                    jnp.sum(cntd.reshape((R, Dq) + tuple(moved)),
                            axis=(0, 1)), -1, ax)
                means.append(s / jnp.maximum(cnt, 1.0))
        g_agg = jax.tree_util.tree_unflatten(mtd, means)
        new_master = jax.tree_util.tree_map(
            lambda x, gg: (x.astype(jnp.float32) - gg).astype(x.dtype),
            state.master, g_agg)
        # ---- dequeue: zero applied vals, reset sentinels ---------------
        it = iter(new_bufs)
        deq = []
        for kind, _ax, moved in meta:
            if kind == "dense":
                buf = next(it)
                lm = landing.reshape((R, Dq) + (1,) * (buf.ndim - 2))
                deq.append(jnp.where(lm, jnp.zeros_like(buf), buf))
                continue
            idx_buf = next(it)
            val_buf = next(it)
            lm = landing.reshape((R, Dq) + (1,) * (val_buf.ndim - 2))
            deq.append(jnp.where(lm, jnp.full_like(idx_buf, moved[-1]),
                                 idx_buf))
            deq.append(jnp.where(lm, jnp.zeros_like(val_buf), val_buf))
        arrive = jnp.where(landing, -1, arrive)
        tau = jnp.where(landing, 0, tau)
        # ---- broadcast to workers whose payload landed -----------------
        b = jnp.any(landing, axis=1) & alive_r

        def pick(x, o):
            bb = jnp.broadcast_to(x[None], o.shape).astype(o.dtype)
            sel = jnp.where(b.reshape((-1,) + (1,) * (o.ndim - 1)), bb, o)
            return jax.lax.with_sharding_constraint(
                sel, NamedSharding(mesh, P(tuple(daxes))))

        new_local = jax.tree_util.tree_map(pick, new_master, half_all)
        new_view = jax.tree_util.tree_map(pick, new_master, view)
        n_recv = jnp.sum(b.astype(jnp.float32))
        down_cost = n_recv * jnp.float32(down.dense_bits(state.master))
        return state._replace(
            master=new_master, local=new_local, memory=memory,
            inner=inner, view=new_view, step=state.step + 1,
            bits=state.bits + wire_bits,
            rounds=state.rounds + jnp.any(landing).astype(jnp.int32),
            bits_down=state.bits_down + down_cost,
            inflight=tuple(deq), arrive_at=arrive, inflight_tau=tau,
        ), loss

    fault_sync_step = (fault_sync_step_sparse if wire == "sparse_allgather"
                       else fault_sync_step_dense)

    # ---- init ------------------------------------------------------------
    def init_fn(params):
        def body(p):
            local = _expand(p)
            memory = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), local)
            inner = _expand(inner_opt.init(p))
            out = [p, local, memory, inner, local]
            if wire == "dense_psum":
                out.append(jax.tree_util.tree_map(
                    lambda x: jnp.zeros((1, Dq) + x.shape, jnp.float32),
                    p))
            out.append(jnp.full((1, Dq), -1, jnp.int32))
            out.append(jnp.zeros((1, Dq), jnp.int32))
            return tuple(out)

        nq = 1 if wire == "dense_psum" else 0
        mapped = jax.shard_map(
            body, mesh=mesh, in_specs=(P(),),
            out_specs=(P(),) + (worker_specs,) * (4 + nq + 2),
            axis_names=manual, check_vma=check_vma,
        )
        out = jax.jit(mapped)(params)
        master, local, memory, inner, view = out[:5]
        if wire == "dense_psum":
            inflight, arrive, tau = out[5], out[6], out[7]
        else:
            arrive, tau = out[5], out[6]
            # compact wire buffers: [R, depth, ..., kcap] per sparse
            # leaf (idx at the out-of-row sentinel, vals zero), a dense
            # [R, depth, leaf] buffer per dense-payload leaf — sized
            # exactly like axis_topk_compact's emissions
            from repro.kernels import dispatch as dsp
            leaves = jax.tree_util.tree_leaves(master)
            meta = compressor.leaf_meta(master, param_specs)
            plans = compressor._plans(len(leaves))
            bufs = []
            for leaf, (kind, ax, moved), plan in zip(leaves, meta, plans):
                if kind == "dense":
                    bufs.append(jnp.zeros((R, Dq) + leaf.shape,
                                          jnp.float32))
                    continue
                n = moved[-1]
                kcap = dsp.capacity(resolve_k(plan[1], n), n)
                shape = (R, Dq) + tuple(moved[:-1]) + (kcap,)
                bufs.append(jnp.full(shape, n, jnp.int32))
                bufs.append(jnp.zeros(shape, jnp.float32))
            inflight = tuple(bufs)
        return DistQsparseState(
            master=master, local=local, memory=memory, inner=inner,
            step=jnp.zeros((), jnp.int32),
            bits=jnp.zeros((), jnp.float32),
            rounds=jnp.zeros((), jnp.int32),
            view=view, down_memory=None,
            bits_down=jnp.zeros((), jnp.float32),
            inflight=inflight, arrive_at=arrive, inflight_tau=tau,
        )

    return init_fn, fault_local_step, fault_sync_step


def make_dist_fault_round(
    grad_fn: Callable,
    inner_opt: GradientTransform,
    compressor: ShardCompressor,
    lr_schedule: Callable,
    mesh,
    data_axes: Sequence[str] = ("data",),
    param_specs=None,
    *,
    queue_depth: int,
    aggregate: str = "mean_R",
    wire: str = "dense_psum",
    staleness_weight: str = "uniform",
):
    """Round program for the mesh fault runtime: rounds close at every
    *event* step (``rounds.compile_fault_rounds``), so the scanned head
    is pure fault-local steps and the tail one fault-sync step.

    Returns ``(init_fn, round_fn)`` with ``round_fn(state,
    batch_block, row_block, key) -> (state, losses[L], key)`` —
    ``row_block`` an ``engine.FaultRow`` of [L, R] arrays (stacked per
    step, ``engine.index_rows(rows, slice(start, stop))``).  Bit-for-bit
    the per-step fault trajectories.
    """
    init_fn, fls, fss = make_dist_fault_steps(
        grad_fn, inner_opt, compressor, lr_schedule, mesh, data_axes,
        param_specs, queue_depth=queue_depth, aggregate=aggregate,
        wire=wire, staleness_weight=staleness_weight)
    from repro.core.engine import FaultRow, donated_jit

    def round_program(state, batch_block, row_block, key):
        rows = FaultRow(*(jnp.asarray(x) for x in row_block))

        def body(carry, xs):
            state, key = carry
            batch, row = xs
            key, sub = jax.random.split(key)
            state, loss = fls(state, batch, row, sub)
            return (state, key), loss

        head_b = jax.tree_util.tree_map(lambda x: x[:-1], batch_block)
        head_r = FaultRow(*(x[:-1] for x in rows))
        tail_b = jax.tree_util.tree_map(lambda x: x[-1], batch_block)
        (state, key), head_losses = jax.lax.scan(
            body, (state, key), (head_b, head_r))
        key, sub = jax.random.split(key)
        tail = FaultRow(*(x[-1] for x in rows))
        state, tail_loss = fss(state, tail_b, tail, sub)
        return (state, jnp.concatenate([head_losses,
                                        tail_loss[None]]), key)

    return init_fn, donated_jit(round_program)


def _zero1_axis(shape, spec, W: int):
    """ZeRO-1 shard axis for a leaf: the first axis that divides by the
    worker count and is unsharded in the TP spec; None when no axis
    qualifies (leaf stays replicated).  Layer-stacked leaves [L, ...]
    with L !% W fall through to their (usually large) inner dims."""
    entries = (list(spec) + [None] * len(shape)) if spec is not None \
        else [None] * len(shape)
    for ax, n in enumerate(shape):
        if entries[ax] is None and n % W == 0 and n >= W:
            return ax
    return None


def _allgather_axis(x, daxes, axis):
    """ZeRO-1: gather the shards spread over the worker axes."""
    g = x
    for a in reversed(daxes):
        g = jax.lax.all_gather(g, a, axis=axis, tiled=True)
    return g


def _shard_axis(x, daxes, axis):
    """Keep only this worker's slice along ``axis`` (inverse gather)."""
    n = 1
    idx = 0
    for a in daxes:
        size = jax.lax.axis_size(a)
        idx = idx * size + jax.lax.axis_index(a)
        n *= size
    shard = x.shape[axis] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * shard, shard, axis=axis)
