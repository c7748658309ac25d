"""Share of the traced window in which a chip ran no operation, the
mean over the chips used: 1 - busy / window, busy the union of every
``XLA Ops`` event on the chip.  Layer: the device and the round driver
(one dispatch and one ``block_until_ready`` per round)."""

EVENTS = [".*"]


def read(trace, facts):
    if facts.get("kind") != "train":
        return None
    return 100.0 * trace.idle_share
