"""Model FLOP/s utilization of training: forward and backward FLOPs of
every token trained in the traced window (``work.train_flops_per_token``,
recomputation not counted), over the window's length on the device
trace, the chips and their bf16 peak.  Layer: the model step inside the
round program.  Reads no trace event by name, only the window."""

from bench import work

EVENTS = []


def read(trace, facts):
    if facts.get("kind") != "train":
        return None
    peak = work.peaks(facts["device_kind"])["bf16_flops_per_s"]
    flops = facts["flops_per_token"] * facts["tokens"]
    return 100.0 * flops / (trace.window_s * facts["chips"] * peak)
