"""Host milliseconds per fused triage launch, from the call to the
readback of its outputs (``triage_launch_s`` over the report's
``kernel_launches``): the round trip that the trace's device time per
launch is a part of."""
from chipbench import stages


def read(ctx):
    s = stages.seconds(ctx, ("triage_launch_s",))
    launches = sum(c["report"]["kernel_launches"] for c in ctx["calls"])
    if s is None or not launches:
        return None
    return 1e3 * s / launches
