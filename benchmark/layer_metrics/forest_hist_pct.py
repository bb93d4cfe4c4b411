"""Share of the traced forests' device seconds spent in the histogram
programs (the jitted modules whose name holds `forest_hist`:
ops/forest._forest_hist), of all jitted modules' seconds. Nothing where the
trace holds no such module."""


def read(ctx):
    modules = ctx["trace"]["modules"]
    total = sum(m["seconds"] for m in modules.values())
    mine = [m["seconds"] for name, m in modules.items()
            if "forest_hist" in name]
    if not total or not mine:
        return None
    return 100.0 * sum(mine) / total
