"""Checkpoint writer (`CheckpointManager._write`): a write's encode less
its base read (the `ckpt.encode` spans' self time: the codecs and the
XOR kernel's round trips), `ckpt.stats[*].encode_s`, mean over the
writes of the saves begun in the window.  A program whose writes carry
no such split reads nothing."""
from statistics import fmean


def read(r):
    s = r.get("ckpt_stats") or []
    if not s or "encode_s" not in s[0]:
        return None
    return fmean(x["encode_s"] for x in s)
