"""A resume's share of the chips' peak: the model FLOPs of the one step
it ends with, over the whole resume (restore to the end of that step)
at the cell's chips times the bf16 peak.  The bound on what a kernel's
roofline gain on the restore path can give."""


def read(r):
    res = r["e2e"].get("resume_s")
    if not res or r["trace"] is None:
        return None
    flops = r["flops_per_token"] * r["tokens_per_step"]
    return 100.0 * flops / res / (r["chips"] * r["peaks"]["bf16_flops_per_s"])
