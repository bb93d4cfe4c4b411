"""Bytes of filter data sent host -> device per window dispatch, from the
program's own counter `pio_filter_h2d_bytes_total` as a difference over the
window: the queries' category codes and flat item lists, and each new
availability bitmap once. Nothing where the program has no such counter."""


def read(ctx):
    return ctx["window"].get("filter_h2d_bytes_per_dispatch")
