"""l_step_drain_ms: host milliseconds per LC iteration from the end of the
L step's dispatches until the device has run them (the trainer's
``lc.drain`` span): how much device work the host had queued ahead."""

from spansums import sums


def read(ctx):
    s = sums(ctx, host=["lc.drain"])
    return None if s is None else s[0]["lc.drain"] / s[2]
