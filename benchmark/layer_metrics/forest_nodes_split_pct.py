"""Nodes that split over the nodes the depth allows (trees x (2^depth - 1)),
summed over the window's forests, from the program's own count
(`forest_grow`'s telemetry, counted on the device at every level). Nothing
where the window counted none."""


def read(ctx):
    w = ctx["window"]
    if not w.get("nodes_allowed"):
        return None
    return 100.0 * w["nodes_split"] / w["nodes_allowed"]
