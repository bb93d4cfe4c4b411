"""Mean wall from the moment a dispatch's device call was enqueued (`begin`
returned) to its answer lying on the host (d2h ready), over the window's
records in the program's serving account: the wait behind the programs ahead
of it on the device, its own device time, the sit in the completion queue and
the copy back."""

from benchmark.lib import account


def read(ctx):
    return account.mean_ms(account.window_dispatches(ctx),
                           "t_ready", "t_begin")
