"""c_step_roofline: the C step's compulsory HBM traffic (read each
compressed weight and its multiplier once, write Theta and Delta(Theta)
once: 16 bytes a weight) at the chip's peak bandwidth, over the device
time per call of the C-step program (``jit(_c_step_impl)``), in %."""

import flops


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls, secs = tr.modules(r"_c_step_impl")
    if not calls or secs <= 0:
        return None
    floor_s = flops.cstep_bytes(ctx["compressed_weights"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (secs / calls)
