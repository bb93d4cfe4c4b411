"""What of the slice's query_p50_ms (from the scheduled send, by the load
generator's clock) is not inside the server: the generator's lateness, the
sockets, accept and the start of the handler's thread. query_p50_ms less
serve_request_server_ms_p50; a difference of medians, not a median of
differences (the two clocks meet in no single request)."""

from benchmark.lib import account


def read(ctx):
    inside = account.server_ms_p50(ctx)
    total = ctx["window"].get("query_p50_ms")
    if inside is None or total is None:
        return None
    return total - inside
