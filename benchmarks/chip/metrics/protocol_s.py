"""Safe point / 2PC (`RankAgent.safe_point`): per save, the safe point's
time less the `save_async` call inside it, mean over the saves."""
from statistics import fmean


def read(r):
    s = r["saves"]
    return fmean(x["safe_point_s"] - x["save_async_s"] for x in s) if s else None
