"""Image read (`CheckpointManager.restore`: read, verify, fold the
chain), host clock around the call, mean over the resumes."""
from statistics import fmean


def read(r):
    s = r["resumes"]
    return fmean(x["read_s"] for x in s) if s else None
