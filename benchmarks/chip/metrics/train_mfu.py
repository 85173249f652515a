"""Train step (`training/step.py`): model FLOPs utilisation.  Forward
and backward FLOPs per token of the published shapes (no recompute, no
padded heads, causal attention at half) times the traced run's tokens
per second, over the device kind's bf16 peak."""


def read(r):
    tps = r["e2e"].get("tokens_per_s")
    if not tps or r["trace"] is None:
        return None
    return 100.0 * r["flops_per_token"] * tps / r["peaks"]["bf16_flops_per_s"]
