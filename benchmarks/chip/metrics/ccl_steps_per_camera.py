"""Label fixpoint steps per camera labelled: ``ccl_steps`` over
``ccl_cameras`` (the program's counters in each call's report: the steps
each camera's fixpoint took, and the cameras labelled on ticks that ran
the CCL program), over the traced window.  A program without the
counters reads nothing."""


def read(ctx):
    reports = [c["report"] for c in ctx["calls"]
               if "ccl_cameras" in c["report"]]
    cameras = sum(r["ccl_cameras"] for r in reports)
    if not cameras:
        return None
    return sum(r["ccl_steps"] for r in reports) / cameras
