"""lc_boundary_ms: host milliseconds per LC iteration outside the L step,
the drain and the C step (the trainer's ``lc.iteration`` span less its
``lc.l_step``, ``lc.drain`` and ``lc.c_step``): the multiplier step, the
monitors and the host syncs of the LC boundary."""

from spansums import sums

PARTS = ("lc.l_step", "lc.drain", "lc.c_step")


def read(ctx):
    s = sums(ctx, host=("lc.iteration",) + PARTS)
    if s is None:
        return None
    ms = s[0]
    return (ms["lc.iteration"] - sum(ms[n] for n in PARTS)) / s[2]
