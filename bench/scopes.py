"""Device time of the round program's stages, read from the named scopes
the program puts into its HLO (``ROUND_SCOPES`` in
``src/repro/core/distributed.py``).

A profiler trace names each ``XLA Ops`` event by its HLO text
(``bench/trace.py``); the event's metadata also carries a ``tf_op``
stat, the operation's HLO ``op_name``, e.g.
``jit(round_core)/while/body/closed_call/qsparse.grad/transpose(jvp())/
while``.  ``jax.profiler.ProfileData`` exposes event stats but not
metadata stats, so :func:`op_names` reads them from the ``.xplane.pb``
with a small protobuf wire-format reader (field numbers of
``tsl/profiler/protobuf/xplane.proto``).  An operation's scope is the
first ``/``-separated component of its ``tf_op`` that starts with
``qsparse.``, else ``""``; the scopes never nest.  Times are self times
(``trace.self_times``), so the scopes and the unscoped rest add up to
the busy time.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs a training cell once with the profiler on, as ``bench/run.py
--trace 1`` does but without the output check, and prints one JSON
line: ``train_tokens_per_s`` of the traced window, the cell's per-layer
metrics, busy and window seconds, device seconds per scope (total and
per round), the unscoped operations over 1 ms a round, and
``model_step_ms``, ``optimizer_ms`` and ``compress_ms``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

T0 = time.perf_counter()

SCOPE_PREFIX = "qsparse."
UNSCOPED = "(none)"

# xplane.proto field numbers
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_MAP_VALUE = 2
_META_ID, _META_NAME, _META_STATS = 1, 2, 5
_STAT_META_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field (a
    string, bytes or a nested message), raw bytes for a fixed-width
    one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(plane_fields, number: int) -> list:
    return [dict(fields(v)).get(_MAP_VALUE, b"")
            for k, v in plane_fields if k == number]


def _plane_op_names(plane_fields) -> dict:
    """{event name: tf_op} of one plane's event metadata."""
    stat_names = {}
    for meta in _map_values(plane_fields, _PLANE_STAT_META):
        f = dict(fields(meta))
        stat_names[f.get(_META_ID, 0)] = bytes(f.get(_META_NAME, b"")).decode()
    tf_op = {i for i, name in stat_names.items() if name == "tf_op"}
    out = {}
    for meta in _map_values(plane_fields, _PLANE_EVENT_META):
        name, op = "", None
        for k, v in fields(meta):
            if k == _META_NAME:
                name = bytes(v).decode()
            elif k == _META_STATS:
                stat = dict(fields(v))
                if stat.get(_STAT_META_ID) not in tf_op:
                    continue
                if _STAT_STR in stat:
                    op = bytes(stat[_STAT_STR]).decode()
                elif _STAT_REF in stat:
                    op = stat_names.get(stat[_STAT_REF])
        if op is not None:
            out[name] = op
    return out


def op_names(path) -> dict:
    """{device: {event name: tf_op}} for the ``/device:TPU:n`` planes of
    one ``.xplane.pb``."""
    from bench.trace import DEVICE_PLANE
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for k, plane in fields(data):
        if k != _SPACE_PLANES:
            continue
        pf = list(fields(plane))
        name = next((bytes(v).decode() for n, v in pf if n == _PLANE_NAME),
                    "")
        m = DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = _plane_op_names(pf)
    return out


def scope_of(tf_op: str) -> str:
    """The first ``qsparse.*`` component of ``tf_op``, else ``""``."""
    return next((c for c in tf_op.split("/") if c.startswith(SCOPE_PREFIX)),
                "")


@dataclasses.dataclass
class Scoped:
    """A reduced trace (``bench.trace.Reduced``) with the ``tf_op`` of
    each device's operations."""
    reduced: object
    tf_ops: dict        # device -> {event name: tf_op}

    def scope(self, event) -> str:
        return scope_of(self.tf_ops.get(event.device, {}).get(event.name, ""))

    def _self_times(self):
        from bench.trace import self_times
        for d in range(self.reduced.n_devices):
            yield from self_times(self.reduced.device_ops(d))

    @functools.cached_property
    def _scope_times_s(self) -> dict:
        out: dict = {}
        for ev, t in self._self_times():
            s = self.scope(ev)
            out[s] = out.get(s, 0.0) + t
        return {k: v / self.reduced.n_devices / 1e9 for k, v in out.items()}

    def scope_time_s(self, scope: str) -> float:
        """Self seconds of the operations under ``scope`` (``""``: under
        none), the mean over chips."""
        return self._scope_times_s.get(scope, 0.0)

    @property
    def has_scopes(self) -> bool:
        return any(self._scope_times_s)

    def scopes(self) -> list:
        """[[scope, seconds]] for every scope and ``(none)``, longest
        first."""
        items = [[s or UNSCOPED, t] for s, t in self._scope_times_s.items()]
        return sorted(items, key=lambda kv: -kv[1])

    def unscoped_ops(self, min_s: float = 0.0) -> list:
        """[[operation, seconds]] of the operations under no scope with at
        least ``min_s`` self seconds (mean over chips), longest first."""
        out: dict = {}
        for ev, t in self._self_times():
            if not self.scope(ev):
                out[ev.name] = out.get(ev.name, 0.0) + t
        n = self.reduced.n_devices * 1e9
        items = [[k[:160], v / n] for k, v in out.items() if v / n >= min_s]
        return sorted(items, key=lambda kv: -kv[1])


def trace_file(trace_dir) -> str:
    """The one ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{files}")
    return files[0]


def load(trace_dir, n_devices: int) -> Scoped:
    """The trace under ``trace_dir``, reduced and scoped."""
    from bench import trace
    return Scoped(trace.load(trace_dir, n_devices),
                  op_names(trace_file(trace_dir)))


def model_step_ms(scoped: Scoped, facts: dict):
    """Model forward and backward (``qsparse.grad``) per step."""
    if not scoped.has_scopes:
        return None
    return 1e3 * scoped.scope_time_s("qsparse.grad") / facts["steps"]


def optimizer_ms(scoped: Scoped, facts: dict):
    """Momentum, half step and master update per step."""
    if not scoped.has_scopes:
        return None
    t = (scoped.scope_time_s("qsparse.optimizer")
         + scoped.scope_time_s("qsparse.master_update"))
    return 1e3 * t / facts["steps"]


def compress_ms(scoped: Scoped, facts: dict):
    """The uplink compressor (accumulator, Top_k, masking) per sync:
    one compression of every leaf."""
    if not scoped.has_scopes:
        return None
    return 1e3 * scoped.scope_time_s("qsparse.compress") / facts["syncs"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="copy the trace's .xplane.pb to this file")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import run as brun
    brun._paths()
    cell = brun.Cell(brun.load_json(brun.CHECKOUT / "BENCHMARK.json"),
                     args.workload)
    devices = brun.require_chip(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    driver = brun.load_module(brun.BENCH / "drivers" / f"{cell.driver}.py")
    trace_dir = brun.OUT / "scopes" / f"{cell.name}-{args.seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = driver.run(config=cell.config, traffic=cell.traffic,
                     seed=args.seed, seconds=args.seconds, devices=devices,
                     t0=T0, trace_dir=trace_dir)
    scoped = load(trace_dir, n_devices=len(devices))
    if args.keep:
        shutil.copyfile(trace_file(trace_dir), args.keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = scoped.reduced
    facts = dict(run.facts, chips=len(devices),
                 device_kind=devices[0].device_kind)
    rounds = facts["rounds"]
    result = {
        "workload": cell.name, "seed": args.seed,
        "attempted": run.attempted, "failed": run.failed,
        "train_tokens_per_s": run.metrics["train_tokens_per_s"],
        "per_layer": {k: v["value"] for k, v in
                      brun.read_per_layer(cell, facts, red).items()},
        "rounds": rounds, "steps": facts["steps"], "syncs": facts["syncs"],
        "busy_s": red.busy_s, "window_s": red.window_s,
        "scopes": scoped.scopes(),
        "scopes_ms_per_round": [[s, 1e3 * t / rounds]
                                for s, t in scoped.scopes()],
        "unscoped_over_1ms_per_round": [
            [k, 1e3 * t / rounds]
            for k, t in scoped.unscoped_ops(1e-3 * rounds)],
        "model_step_ms": model_step_ms(scoped, facts),
        "optimizer_ms": optimizer_ms(scoped, facts),
        "compress_ms": compress_ms(scoped, facts),
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    main()
