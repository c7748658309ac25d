"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each chip is one plane ``/device:TPU:<n>`` (TPU v5e, jax 0.9).  Its
``XLA Ops`` line holds one event per HLO operation run on the chip,
named by the operation's HLO text (``%fusion.3 = bf16[...] fusion(...)``;
a Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``, named after the enclosing
scope, so a metric finds its kernel by the signature of its operands and
results).  Control-flow operations (``%while.N``) are events too and
enclose the operations of their body.  The host's ``python`` line holds
the harness's own spans (``bench.*``).

From these: each chip's busy time is the union of its operation
intervals; the traced window runs from the first operation to the last
over all chips; an operation's self time leaves out the operations it
encloses; idle gaps are the stretches of the window in which a chip ran
nothing, each named by the harness span that covered it.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
HOST_SPAN = re.compile(r"^bench\.")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    device: int = -1

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events) -> list:
    """(event, self ns) for events of one chip that may nest: each
    event's duration less the operations it encloses (an operation that
    only overlaps another, as an asynchronous one may, is not enclosed)."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            out.append(tuple(stack.pop()))
        if stack and ev.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] -= ev.dur_ns
        stack.append([ev, ev.dur_ns])
    out += [tuple(x) for x in stack]
    return out


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, start: float, end: float) -> list:
    """(start, end) stretches of [start, end] that no interval covers."""
    out, t = [], start
    for s, e in union(intervals):
        if s > t:
            out.append((t, min(s, end)))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    ops: list            # device operations (Event, device >= 0)
    host: list           # harness spans on the host (Event)
    n_devices: int

    @property
    def start_ns(self) -> float:
        return min(e.start_ns for e in self.ops)

    @property
    def end_ns(self) -> float:
        return max(e.end_ns for e in self.ops)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def device_ops(self, device: int) -> list:
        return [e for e in self.ops if e.device == device]

    @property
    def busy_s(self) -> float:
        """Busy seconds, the mean over the chips used."""
        per = [covered((e.start_ns, e.end_ns) for e in self.device_ops(d))
               for d in range(self.n_devices)]
        return sum(per) / self.n_devices / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def matching(self, pattern: str) -> list:
        rx = re.compile(pattern)
        return [e for e in self.ops if rx.search(e.name)]

    def time_s(self, pattern: str) -> float:
        """Device seconds of the operations whose names match
        ``pattern``, summed over chips and divided by their number."""
        return sum(e.dur_ns for e in self.matching(pattern)) \
            / self.n_devices / 1e9

    def per_name_s(self) -> dict:
        """Self seconds per operation name, the mean over chips."""
        out: dict = {}
        for d in range(self.n_devices):
            for e, t in self_times(self.device_ops(d)):
                out[e.name] = out.get(e.name, 0.0) + t
        return {k: v / self.n_devices / 1e9 for k, v in out.items()}

    def idle_gaps(self, device: int = 0) -> list:
        """(what the host was doing, seconds) of each idle gap of one
        chip, longest first: the innermost harness span that covered
        the gap's middle, else ``outside bench spans``."""
        ivs = [(e.start_ns, e.end_ns) for e in self.device_ops(device)]
        out = []
        for s, e in gaps(ivs, self.start_ns, self.end_ns):
            mid = 0.5 * (s + e)
            spans = [h for h in self.host if h.start_ns <= mid <= h.end_ns]
            name = (min(spans, key=lambda h: h.dur_ns).name if spans
                    else "outside bench spans")
            out.append((name, (e - s) / 1e9))
        return sorted(out, key=lambda g: -g[1])

    def breakdown(self, top: int = 10) -> dict:
        """The operations with the most self time (names cut to their
        first 160 characters) and the longest idle gaps."""
        ops = sorted(self.per_name_s().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k[:160], v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:top]]}


def from_profile(pd, n_devices: int) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(ev.name, ev.start_ns, ev.end_ns, dev)
                            for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [Event(ev.name, ev.start_ns, ev.end_ns)
                         for ev in line.events if HOST_SPAN.match(ev.name)]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return Reduced(ops=ops, host=host, n_devices=n_devices)


def load(trace_dir, n_devices: int) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{files}")
    return from_profile(ProfileData.from_file(files[0]), n_devices)
