"""l_step_ms: device milliseconds per call of the train-step program
(``jit(train_step)``) in the trace."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls, secs = tr.modules(r"train_step")
    return 1e3 * secs / calls if calls else None
