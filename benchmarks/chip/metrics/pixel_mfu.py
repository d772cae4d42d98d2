"""The whole tick's share of the chip's bf16 peak, in percent: the CQ
classifier's forward FLOPs for every crop scored in the traced window,
over the window's length times the peak."""
from counts import classifier


def read(ctx):
    n = ctx.get("crops_scored", 0)
    spec = ctx["config"].get("classifier")
    if not n or spec is None:
        return None
    flops = classifier.forward_flops(spec, n, ctx["crop_tokens"])
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
