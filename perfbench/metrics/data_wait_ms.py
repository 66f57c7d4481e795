"""data_wait_ms: host milliseconds per optimizer step spent fetching the
step's batch (the trainer's ``lc.step.data`` span), over the window's LC
iterations."""

from spansums import sums


def read(ctx):
    s = sums(ctx, host=["lc.step.data"], counts=["lc.step"])
    if s is None or not s[1]["lc.step"]:
        return None
    return s[0]["lc.step.data"] / s[1]["lc.step"]
