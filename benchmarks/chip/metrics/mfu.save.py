"""The whole save cell's share of the chips' peak: forward and backward
FLOPs per token of the published shapes times the traced run's tokens
per second over the window, stalls included, over the cell's chips
times the bf16 peak.  The bound on what a kernel's roofline gain on the
save path can give."""


def read(r):
    tps = r["e2e"].get("tokens_per_s")
    if not tps or r["trace"] is None:
        return None
    return 100.0 * r["flops_per_token"] * tps / (
        r["chips"] * r["peaks"]["bf16_flops_per_s"])
