"""Checkpoint writer (`CheckpointManager._write`): encode, digest and
file write of one image, `ckpt.stats[*].write_s`, mean over the writes
of the saves begun in the window."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    return fmean(x["write_s"] for x in s) if s else None
