"""Roofline share of the Top_k compressors in training: the least time
their work needs (``work.compress_bytes``: each leaf's accumulator read
once, the selection and error memory or the compact survivors written
once, at the chip's HBM bandwidth; the bisection has no matrix work)
over the device time of the compressor kernels' events.  Layer:
``kernels/topk_compress.py`` through ``kernels/dispatch.py``."""

from bench import work

_T = r"\[\d+,\d+\]\{[^}]*\}"
_CNT = r"s32\[\d+,1\]\{[^}]*\}"
_KERNEL = r"\) custom-call\(.*custom_call_target=\"tpu_custom_call\""
#: the two Pallas kernels' ``XLA Ops`` events (v5e, jax 0.9), by their
#: results: ``topk_compress`` gives (selection f32[rows, n], memory
#: f32[rows, n], count s32[rows, 1]); ``topk_compact`` gives (indices
#: s32[rows, kcap], values f32[rows, kcap], memory f32[rows, n], count
#: s32[rows, 1]).  Seen on the chip as e.g. ``%round_core.16 =
#: (f32[20480,7168]{1,0:T(8,128)}, f32[20480,7168]{1,0:T(8,128)},
#: s32[20480,1]{1,0:T(8,128)}) custom-call(f32[20480,7168]...),
#: custom_call_target="tpu_custom_call"``.
EVENTS = [rf"^%\S+ = \(f32{_T}, f32{_T}, {_CNT}{_KERNEL}",
          rf"^%\S+ = \(s32{_T}, f32{_T}, f32{_T}, {_CNT}{_KERNEL}"]


def read(trace, facts):
    if facts.get("kind") != "train":
        return None
    t = sum(trace.time_s(p) for p in EVENTS)
    if t <= 0:
        return None
    hbm = work.peaks(facts["device_kind"])["hbm_bytes_per_s"]
    least = facts["syncs"] * facts["compress_bytes_per_sync"] / hbm
    return 100.0 * least / t
