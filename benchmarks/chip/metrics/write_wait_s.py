"""Checkpoint save: time `save_async` spent waiting for the previous
background write, its call time less its snapshot, mean over saves."""
from statistics import fmean


def read(r):
    saves, stats = r["saves"], r.get("ckpt_stats") or []
    if not saves or len(stats) < len(saves):
        return None
    return fmean(max(s["save_async_s"] - st["snapshot_s"], 0.0)
                 for s, st in zip(saves, stats))
