"""sim.mfu: the sim fit's model FLOP/s over the chips' bf16 peak, in %.

Model FLOPs of one round: 3 x forward FLOPs per image (forward and
backward) for every image trained (clinics x local steps x batch), plus
forward FLOPs for every real val image scored. Forward FLOPs per image
are counted from the configuration's shapes (``chipbench.flops``). The
time is the traced window's wall time.
"""
from chipbench import flops


def flops_per_round(ctx) -> int:
    c = ctx.config
    fwd = flops.forward_flops(ctx.model.forward, ctx.model.param_shapes(),
                              (c["image_size"], c["image_size"], 3))
    trained = ctx.n_clients * ctx.local_steps * c["batch"]
    return 3 * fwd * trained + fwd * ctx.val_images


def read(ctx):
    if ctx.rounds == 0 or ctx.trace.window_s <= 0:
        return None
    peak = ctx.peaks["bf16_flops_per_s"] * ctx.chips
    return 100.0 * flops_per_round(ctx) * ctx.rounds / (
        ctx.trace.window_s * peak)
