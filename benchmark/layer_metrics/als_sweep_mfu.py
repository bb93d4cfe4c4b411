"""The whole ALS iteration's share of the chip's peak FLOP/s: the operations
the algorithm needs per iteration (benchmark/lib/counts.py, from the data's
degrees and the rank), over the mean iteration wall of the slice."""


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if not peaks or not w.get("iterations"):
        return None
    per_iteration_s = w["wall_s"] / w["iterations"]
    return (100.0 * ctx["work"]["iteration_flops"] / per_iteration_s
            / peaks["flops_per_s"])
