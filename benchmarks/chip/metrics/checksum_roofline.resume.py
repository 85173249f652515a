"""Kernel `checksum_block_sums` on the verify path of a restore: the
bytes its word arrays and sums occupy over the device kind's HBM
bandwidth, as a share of the kernel's summed device time in the trace
of the window."""


def read(r):
    k = (r["trace"] or {}).get("kernels", {}).get("checksum_block_sums")
    if not k or not k["bytes"] or not k["time_s"]:
        return None
    return 100.0 * k["bytes"] / r["peaks"]["hbm_bytes_per_s"] / k["time_s"]
