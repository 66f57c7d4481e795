"""lc_mfu: model FLOPs of the traced LC iteration's L steps (forward and
backward, from the configuration's sizes, recomputation not counted) over
the traced window's seconds times the chip's peak bf16 FLOP/s, in %."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    work = ctx["tokens_traced"] * ctx["train_flops_per_token"]
    return 100.0 * work / (tr.window_s * ctx["peaks"]["bf16_flops_per_s"])
