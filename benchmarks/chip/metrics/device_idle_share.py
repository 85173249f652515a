"""Device: share of the measured window in which no operation ran on
the chip (1 - union of the XLA op intervals over the window)."""


def read(r):
    t = r["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
