"""param_stats_roofline: the stat-upload kernel's share of its roofline, in %.

The kernel (``kernels/param_stats.param_stats_batched``, a Pallas call
per client-stacked tensor) reduces every parameter of every clinic to
a mean and a variance once a round. The algorithm needs each element
read once: bytes = clinics x parameters x 4 (float32), against the
chip's HBM bandwidth; its 3 FLOPs an element never bound it. The time
is the summed device time of the kernel's events in the trace. The
bytes are the algorithm's, not the program's padded tiles, so a kernel
that stops padding reads higher, and never above 100%.
"""
from chipbench import flops, trace

# the kernel's ops are custom calls named after the Pallas call
KERNEL = "%param_stats_batched"


def is_kernel(name: str) -> bool:
    return trace.short_name(name).startswith(KERNEL)


def read(ctx):
    kernel_s = trace.op_seconds(ctx.trace, is_kernel)
    if kernel_s <= 0 or ctx.rounds == 0:
        return None
    need = flops.param_bytes(ctx.model.param_shapes(), ctx.n_clients)
    return 100.0 * need * ctx.rounds / ctx.peaks["hbm_bytes_per_s"] / (
        kernel_s * ctx.chips)
