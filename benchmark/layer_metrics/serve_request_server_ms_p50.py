"""Median wall of a request inside the server: last byte written -
`pio.http.request` start (the request line and headers parsed), over the
window's records in the program's serving account."""

from benchmark.lib import account


def read(ctx):
    return account.server_ms_p50(ctx)
