"""Kernel `delta_xor`: two word arrays read and one written over the
device kind's HBM bandwidth, as a share of the kernel's summed device
time in the trace."""


def read(r):
    k = (r["trace"] or {}).get("kernels", {}).get("delta_xor")
    if not k or not k["bytes"] or not k["time_s"]:
        return None
    return 100.0 * k["bytes"] / r["peaks"]["hbm_bytes_per_s"] / k["time_s"]
