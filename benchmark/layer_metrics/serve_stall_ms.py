"""Milliseconds of the slice inside serving stalls, by the program's own
stall watch (predictionio_tpu/obs/stallwatch.py: requests waiting and no
dispatch made or completed for 0.4 s, or the watch's own tick that late),
`pio_serve_stall_seconds_total` as a difference over the window; 0 in a
window without one. What each stall was (the process's CPU, the machine's
`/proc/stat`, the stacks) is on the run's line of spans. Nothing where the
program has no stall watch."""


def read(ctx):
    return ctx["window"].get("stall_ms")
