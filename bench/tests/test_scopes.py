"""The scope reader (``bench/scopes.py``): the protobuf wire-format
reader on a hand-encoded trace, and the ``tf_op`` of each operation
and the device time per ``qsparse.*`` scope on two traces recorded on a
TPU v5e: ``tiny_v5e.xplane.pb`` (no scopes) and
``scoped_v5e.xplane.pb`` (``record_scoped_trace.py``)."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import scopes
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny_v5e.xplane.pb"
SCOPED = DATA / "scoped_v5e.xplane.pb"
#: self seconds per scope of SCOPED, and its busy seconds (v5e)
GRAD_S, COMPRESS_S, NONE_S = 177.092e-6, 9.384e-6, 51.474e-6
BUSY_S = 237.95e-6


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v)


def _bytes(num: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _entry(num: int, key: int, value: bytes) -> bytes:
    return _bytes(num, _int(1, key) + _bytes(2, value))


def _plane(name: str, events: dict, by_ref: bool = False) -> bytes:
    """An XPlane named ``name`` whose event metadata carry a ``tf_op``
    stat (inline, or as a reference to a stat metadata name)."""
    out = _bytes(2, name) + _entry(5, 7, _int(1, 7) + _bytes(2, "tf_op"))
    for i, (ev, op) in enumerate(events.items(), start=1):
        if by_ref:
            out += _entry(5, 100 + i, _int(1, 100 + i) + _bytes(2, op))
            stat = _int(1, 7) + _int(7, 100 + i)
        else:
            stat = _int(1, 7) + _bytes(5, op)
        # a fixed64 stat before it, as a double would be
        other = _int(1, 8) + _varint(2 << 3 | 1) + bytes(8)
        out += _entry(4, i, _int(1, i) + _bytes(2, ev) + _bytes(5, other)
                      + _bytes(5, stat))
    return out


def test_wire_format_reader(tmp_path):
    space = (_bytes(1, _plane("/device:TPU:0", {
        "%fusion.1 = f32[8]": "jit(f)/while/body/qsparse.grad/mul:",
        "%topk_compress.2 = (f32[8])": "jit(f)/qsparse.compress/"
                                       "topk_compress/pallas_call:"}))
        + _bytes(1, _plane("/device:TPU:1", {"%copy.3": "jit(f)/copy:"},
                           by_ref=True))
        + _bytes(1, _plane("/host:CPU", {"bench.round": "host"}))
        + _bytes(4, "host-0"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    got = scopes.op_names(path)
    assert got == {
        0: {"%fusion.1 = f32[8]": "jit(f)/while/body/qsparse.grad/mul:",
            "%topk_compress.2 = (f32[8])":
                "jit(f)/qsparse.compress/topk_compress/pallas_call:"},
        1: {"%copy.3": "jit(f)/copy:"}}


def test_scope_of():
    assert scopes.scope_of(
        "jit(round_core)/while/body/closed_call/qsparse.grad/"
        "transpose(jvp())/dot_general:") == "qsparse.grad"
    assert scopes.scope_of("jit(round_core)/jit(_threefry_split)/add") == ""
    assert scopes.scope_of("") == ""


def _scoped(path) -> scopes.Scoped:
    red = tr.from_profile(ProfileData.from_file(str(path)), 1)
    return scopes.Scoped(red, scopes.op_names(path))


def test_tiny_v5e_has_no_scopes():
    ops = scopes.op_names(TINY)[0]
    tanh, = [op for name, op in ops.items()
             if name.startswith("%convolution_tanh_fusion ")]
    assert tanh == "jit(<lambda>)/dot_general:"
    sc = _scoped(TINY)
    assert all(sc.scope(e) == "" for e in sc.reduced.ops)
    assert not sc.has_scopes
    assert sc.scopes() == [["(none)", pytest.approx(sc.reduced.busy_s)]]
    facts = {"steps": 4, "syncs": 1}
    assert scopes.model_step_ms(sc, facts) is None
    assert scopes.optimizer_ms(sc, facts) is None
    assert scopes.compress_ms(sc, facts) is None


def test_scoped_v5e_trace():
    """Two runs of a matmul-and-tanh under ``qsparse.grad``, a masking
    pass under ``qsparse.compress`` and an unscoped sum, around a 20 ms
    host sleep: each scope's self time, and the three add up to the
    busy time."""
    sc = _scoped(SCOPED)
    red = sc.reduced
    assert sc.has_scopes
    names = [s for s, _ in sc.scopes()]
    assert set(names) == {"qsparse.grad", "qsparse.compress", "(none)"}
    assert names[0] == "qsparse.grad"       # the matmul is the longest
    assert sum(t for _, t in sc.scopes()) == pytest.approx(red.busy_s)
    assert sc.scope_time_s("qsparse.grad") == pytest.approx(GRAD_S)
    assert sc.scope_time_s("qsparse.compress") == pytest.approx(COMPRESS_S)
    assert sc.scope_time_s("") == pytest.approx(NONE_S)
    facts = {"steps": 2, "syncs": 2}
    assert scopes.model_step_ms(sc, facts) == pytest.approx(
        1e3 * GRAD_S / 2)
    assert scopes.compress_ms(sc, facts) == pytest.approx(
        1e3 * COMPRESS_S / 2)
    # the reduction the harness reads is the one it reads without
    # scopes: the sleep is the longest idle gap, named by its span
    name, gap = red.idle_gaps()[0]
    assert name == "bench.wait" and gap > 0.015
    assert red.busy_s == pytest.approx(BUSY_S)


def test_unscoped_ops_are_listed():
    sc = _scoped(SCOPED)
    listed = sc.unscoped_ops()
    assert listed and sum(t for _, t in listed) == pytest.approx(
        sc.scope_time_s(""))
