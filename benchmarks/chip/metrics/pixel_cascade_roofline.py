"""Fused pixel-cascade kernel time against its roofline, in percent: the
least time the chip could take for the launches of the traced window
(the larger of the bytes the operation needs over peak HBM bandwidth and
its operations over the int8 peak) over the kernel's summed device time.
HBM bounds it: the cascade does a few operations per byte."""
from chipbench import trace as TR
from counts import pixel_cascade

#: the Pallas kernel by its own name, or the custom call in its module
KERNEL = [("_cascade_kernel",), ("custom-call", "pixel_cascade")]


def read(ctx):
    shapes = ctx.get("cascade_shapes") or []
    tr = ctx.get("trace")
    if not shapes or tr is None:
        return None
    t = TR.kernel_seconds(tr, KERNEL, "pixel_cascade_roofline")
    pk = ctx["peaks"]
    need = 0.0
    for b, h, w in shapes:
        nbytes, ops = pixel_cascade.cost(b, h, w)
        need += max(nbytes / pk["hbm_bytes_per_s"], ops / pk["int8_ops"])
    return 100.0 * need / t
