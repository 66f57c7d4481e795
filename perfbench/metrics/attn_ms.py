"""attn_ms: device milliseconds per call of the train-step program spent
in ops under the ``blockwise_attention`` named scope (forward,
rematerialised forward and backward), the union of their intervals.
None where no op carries that scope: a program without it, or a trace
whose ops were given no scope from the programs' HLO text."""

SCOPE = r"^jit\(train_step\)/.*blockwise_attention"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    hits, secs = tr.scoped(SCOPE)
    calls, _ = tr.modules(r"train_step")
    return 1e3 * secs / calls if hits and calls else None
