"""Mean wall a dispatched window sat before the completion thread picked it
up (pio_serve_stage_seconds{stage=completion_wait}), which at steady state
is the wait for the device and its readback."""


def read(ctx):
    return ctx["window"].get("stage_ms", {}).get("completion_wait")
