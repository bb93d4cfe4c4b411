"""The ALS sweep's share of its roofline: the least time a chip could take
for one iteration (the larger of needed operations over peak FLOP/s and
needed bytes over peak HBM bytes/s; at these sizes the bytes bound it, see
PERF.md section 3) over the device's own time in the programs the traced
iterations ran, per iteration, from the trace's line of jitted modules
(summed over the chips, as the needed work is). An iteration runs the half-sweeps and nothing else but the
one-element fetch that closes it, so a stall of the host, or the profiler's
own cost, moves the iteration's wall (`als_sweep_mfu`, the whole step) and
not this."""

from benchmark.lib.counts import roofline_seconds


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    device_s = sum(m["seconds"] for m in ctx["trace"]["modules"].values())
    if not peaks or not w.get("iterations") or not device_s:
        return None
    least_s, _bound = roofline_seconds(ctx["work"]["iteration_flops"],
                                       ctx["work"]["iteration_bytes"], peaks)
    return 100.0 * least_s / (device_s / w["iterations"])
