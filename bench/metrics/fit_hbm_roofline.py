"""The traced jobs' least time on the chip over the device's busy time,
in percent.

The least time is the larger of the bytes the jobs need over the HBM
bandwidth and their operations over the peak rate (``cost.py``, from
shapes and each job's iterations; at K <= 10 the bytes bound it), so it
counts the same work whatever implements a sweep.  Busy time is the
union of the device's operations in the traced window.
"""
from bench import cost


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    if trace is None or peak is None or not ctx["traced_iters"]:
        return None
    n, d = ctx["shape"]
    k = ctx["config"]["k"]
    need_bytes = sum(cost.job_bytes(n, d, k, it) for it in ctx["traced_iters"])
    need_flops = sum(cost.job_flops(n, d, k, it) for it in ctx["traced_iters"])
    least_s, _ = cost.roofline_s(need_bytes, need_flops, peak)
    return 100.0 * least_s / trace["busy_s"]
