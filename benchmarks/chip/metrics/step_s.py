"""Runtime loop (`MANARuntime.run`): mean host-clock time between the
ends of consecutive window steps that held no save."""
from statistics import fmean


def read(r):
    steps = r["steps"]
    clean = [b["t"] - a["t"] for a, b in zip(steps, steps[1:])
             if a["saves_before"] == b["saves_before"]]
    return fmean(clean) if clean else None
