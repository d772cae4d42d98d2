"""Share of the traced window in which no operation ran on the device
(1 - union of device-operation intervals / window), in percent."""
from chipbench import trace as TR


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    busy, _ = TR.device_busy(tr)
    return 100.0 * (1.0 - busy / ctx["window_s"])
