"""Mean wall per served window in one stage of the batcher, from
pio_serve_stage_seconds{stage=...} as a difference over the window. The
stage is the metric's own suffix: `serve_stage_ms.formation` reads
`formation`."""


def read(ctx):
    stage = ctx["metric"].split(".", 1)[1]
    return ctx["window"].get("stage_ms", {}).get(stage)
