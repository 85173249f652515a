"""Runtime rebind: a fresh `MANARuntime` and the rest of its `restore`
(the host-to-device copy), host clock, mean over the resumes."""
from statistics import fmean


def read(r):
    s = r["resumes"]
    return fmean(x["to_device_s"] for x in s) if s else None
