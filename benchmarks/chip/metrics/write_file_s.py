"""Checkpoint writer (`CheckpointManager._write`): a write's file writes
(the `ckpt.file_write` spans: chunk copies and writes),
`ckpt.stats[*].file_s`, mean over the writes of the saves begun in the
window.  A program whose writes carry no such split reads nothing."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    if not s or "file_s" not in s[0]:
        return None
    return fmean(x["file_s"] for x in s)
