"""``lbs_roofline``: the load-balancing search kernel (``lbs_pallas``)
against the HBM roofline: bytes its contract needs over peak bandwidth,
over its device time, in % (``bench/kernels.py``)."""
from bench import kernels


def read(run):
    if run.profile is None or run.window_ns is None or run.peaks is None:
        return None
    lo, hi = run.window_ns
    ops = [e for e in run.profile.ops if lo <= e.start_ns and e.end_ns <= hi]
    return kernels.roofline_share(ops, "lbs_pallas",
                                  run.peaks["hbm_bytes_per_s"])
