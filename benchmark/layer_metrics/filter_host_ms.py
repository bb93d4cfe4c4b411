"""Host time a dispatch spends on the filters outside the live reads: the
`pio.filter.lists` stage (resolving the queries' ids and category names,
padding the lists) plus `pio.filter.constraint_read` (asking the store for
the newest `unavailableItems` `$set`, and parsing it when it is new), mean
per dispatch over every dispatch of the window, taken by the job from the
program's `pio_filter_seconds{stage}` histogram as differences over the
window. Nothing where the program has no such histogram."""


def read(ctx):
    return ctx["window"].get("filter_host_ms")
