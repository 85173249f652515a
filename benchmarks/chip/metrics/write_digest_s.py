"""Checkpoint writer (`CheckpointManager._write`): the digests of a
write's chunks (the `ckpt.digest` spans: host words, upload, kernel, one
scalar back per chunk), `ckpt.stats[*].digest_s`, mean over the writes
of the saves begun in the window.  A program whose writes carry no such
split reads nothing."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    if not s or "digest_s" not in s[0]:
        return None
    return fmean(x["digest_s"] for x in s)
