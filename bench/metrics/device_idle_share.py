"""The device's idle share of the traced window, in percent: 1 minus the
union of its operations' intervals over the window, averaged over the
cell's chips."""
import statistics

from bench import trace_reduce as tr


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    busy = statistics.fmean(tr.busy_ns(ctx["trace"], d, lo, hi)
                            for d in ctx["devices"])
    return 100.0 * (1.0 - busy / (hi - lo))
