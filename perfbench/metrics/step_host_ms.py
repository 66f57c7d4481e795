"""step_host_ms: host milliseconds per optimizer step in the trainer's
``lc.step`` span (fault check, batch, shard and dispatch), over the
window's LC iterations. While the host runs ahead of the device it waits
in dispatch and this follows the device's pace; while the host paces
the loop it is the host's own time per step."""

from spansums import sums


def read(ctx):
    s = sums(ctx, host=["lc.step"], counts=["lc.step"])
    if s is None or not s[1]["lc.step"]:
        return None
    return s[0]["lc.step"] / s[1]["lc.step"]
