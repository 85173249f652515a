"""Checkpoint writer (`CheckpointManager._write`): the delta base's read
and verify inside a write (the `ckpt.base_read` spans, inclusive: file
reads, digests, join), `ckpt.stats[*].base_read_s`, mean over the writes
of the saves begun in the window.  A program whose writes carry no such
split reads nothing."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    if not s or "base_read_s" not in s[0]:
        return None
    return fmean(x["base_read_s"] for x in s)
