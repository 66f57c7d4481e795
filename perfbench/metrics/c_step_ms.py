"""c_step_ms: mean over the window's LC boundaries of the trainer's own
C-step time (``LCTrainer.history[*]["c_step_ms"]``; the device is
drained before and after the C step)."""


def read(ctx):
    vals = [r["c_step_ms"] for r in ctx.get("history", [])]
    return sum(vals) / len(vals) if vals else None
