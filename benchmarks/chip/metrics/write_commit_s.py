"""Checkpoint writer (`CheckpointManager._write`): a write's commit (the
`ckpt.commit` span: manifest, rename, GC of older images),
`ckpt.stats[*].commit_s`, mean over the writes of the saves begun in the
window.  A program whose writes carry no such split reads nothing."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    if not s or "commit_s" not in s[0]:
        return None
    return fmean(x["commit_s"] for x in s)
