"""`serve_stage_ms` for the filtered serve cell: mean wall per served
window in one stage of the batcher, from pio_serve_stage_seconds{stage=...}
as a difference over the window. The stage is the metric's own suffix:
`serve_stage_ms_filtered.formation` reads `formation`. (A reader of its
own because `serve_stage_ms.<stage>` are the other serve cell's names, and
that reader takes everything after the first dot as the stage.)"""


def read(ctx):
    stage = ctx["metric"].split(".", 1)[1]
    return ctx["window"].get("stage_ms", {}).get(stage)
