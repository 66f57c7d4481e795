"""starved_step_share: the share of optimizer steps dispatched after the
device had finished the step before (the trainer's ``lc.step.starved``
counter over its ``lc.step`` count), in %. Near 100 the host paces the
loop; near the floor of one step per drained LC boundary the host runs
ahead of the device."""

from spansums import sums


def read(ctx):
    s = sums(ctx, counts=["lc.step", "lc.step.starved"])
    if s is None or not s[1]["lc.step"]:
        return None
    return 100.0 * s[1]["lc.step.starved"] / s[1]["lc.step"]
