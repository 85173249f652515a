"""Lower half: the first step after a restore (tracing, the executable
loaded from the persistent cache, the step), host clock to
`block_until_ready`, mean over the resumes."""
from statistics import fmean


def read(r):
    s = r["resumes"]
    return fmean(x["first_step_s"] for x in s) if s else None
