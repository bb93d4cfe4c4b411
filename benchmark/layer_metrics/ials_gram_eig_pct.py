"""Share of the traced iterations' device seconds spent in the programs that
take a whole table's Gram and its eigendecomposition (the jitted modules
whose name holds `gram_eig`: ops/als._gram_eig), of all jitted modules'
seconds. Nothing where the trace holds no such module: a program that never
runs one, or no device plane."""


def read(ctx):
    modules = ctx["trace"]["modules"]
    total = sum(m["seconds"] for m in modules.values())
    mine = [m["seconds"] for name, m in modules.items()
            if "gram_eig" in name]
    if not total or not mine:
        return None
    return 100.0 * sum(mine) / total
