"""How late the load generator sent: 95th percentile of actual send minus
scheduled send. Large against the latencies, the generator was starved and
the server looks better than it is."""


def read(ctx):
    return ctx["window"].get("loadgen_late_ms_p95")
