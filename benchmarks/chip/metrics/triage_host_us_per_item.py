"""Host microseconds per finished item in triage outside its launches:
planning the supersteps, packing their slabs and folding their outputs
back (``triage_plan_s`` + ``triage_pack_s`` + ``triage_fold_s``)."""
from chipbench import stages


def read(ctx):
    return stages.us_per_item(
        ctx, ("triage_plan_s", "triage_pack_s", "triage_fold_s"))
