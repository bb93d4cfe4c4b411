"""Share of the slice's wall that this process (the server's) spent inside
generation-2 collections of its garbage collector, from the benchmark's own
`gc.callbacks` hook (benchmark/lib/pauses.py). A collection holds the
interpreter's lock: nothing is answered while it runs, and the requests it
strands are the tail. 0 where the slice saw no such collection."""


def read(ctx):
    return ctx["window"].get("gc2_pause_pct")
