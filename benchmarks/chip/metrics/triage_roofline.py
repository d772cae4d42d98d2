"""Fleet-triage kernel time against its roofline, in percent: the bytes
the operation needs for the real rows and items of every superstep tick
of the traced window, over peak HBM bandwidth, divided by the triage
kernel's summed device time."""
from chipbench import trace as TR
from counts import triage

#: the Pallas kernel by its own name, or, as a TPU trace names it, the
#: HLO custom call that returns three int32 arrays (routes, slots,
#: counts); the CityFlow cells run no other custom call
KERNEL = [("_triage_fleet_kernel",), ("= (s32[", ") custom-call(")]


def read(ctx):
    sizes = ctx.get("ready_sizes") or []
    tr = ctx.get("trace")
    if not sizes or tr is None:
        return None
    t = TR.kernel_seconds(tr, KERNEL, "triage_roofline")
    need = sum(triage.cost(rows, items) for rows, items in sizes)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / t
