"""95th percentile of the seen reads (one per query: the by-entity read of
the user's seen events from the event store, under its 200 ms deadline; the
span `pio.filter.seen_read`) over every query of the window, taken by the
job from the program's `pio_filter_seconds{stage="seen_read"}` histogram as
a difference of bucket counts over the window, interpolated inside the
bucket that holds it (bounds at 2, 3, 4, 6, 8, 12 ms there). Nothing where
the program has no such histogram."""


def read(ctx):
    return ctx["window"].get("seen_read_ms_p95")
