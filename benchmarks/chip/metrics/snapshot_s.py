"""Checkpoint save (`CheckpointManager.save_async`): the synchronous
device-to-host copy, `ckpt.stats[*].snapshot_s`, mean over the saves."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    return fmean(x["snapshot_s"] for x in s) if s else None
