"""Kernels: the three Mosaic flash-attention kernels' share of their
roofline. From the device trace: the summed durations and the number of
calls of flash_fwd, flash_bwd_dq, flash_bwd_dkv on one chip; the least
time for those calls from chipbench/flops.py's operations and bytes at the
call's shape (rows x heads on this chip, seq, head_dim), under the peaks of
the device kind."""

from chipbench import flops


def read(facts):
    kernels = (facts.get("trace") or {}).get("kernels") or {}
    if facts.get("kind") != "fedround" or not kernels:
        return None
    peak = flops.peaks(facts["device_kind"])
    rows_heads = facts["rows_per_chip"] * facts["heads"]
    least = spent = 0.0
    for name, k in kernels.items():
        ops, nbytes = flops.flash_call(name, rows_heads, facts["seq"],
                                       facts["head_dim"])
        least += k["calls"] * flops.least_time(ops, nbytes, peak)[0]
        spent += k["seconds"]
    return 100.0 * least / spent if spent else None
