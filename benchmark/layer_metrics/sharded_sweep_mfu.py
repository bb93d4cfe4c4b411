"""The whole ALS iteration's share of the peak FLOP/s of all the chips that
share the tables: the operations the algorithm needs per iteration
(benchmark/lib/counts.py, from the data's degrees and the rank), over the
mean iteration wall of the slice, over chips x the chip's peak. The job
puts the number of chips among its window's numbers (`als_sweep_mfu`
divides by one chip's peak)."""


def read(ctx):
    w, peaks = ctx["window"], ctx["peaks"]
    if not peaks or not w.get("iterations") or not w.get("chips"):
        return None
    per_iteration_s = w["wall_s"] / w["iterations"]
    return (100.0 * ctx["work"]["iteration_flops"] / per_iteration_s
            / (w["chips"] * peaks["flops_per_s"]))
