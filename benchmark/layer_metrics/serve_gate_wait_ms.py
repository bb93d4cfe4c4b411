"""Mean wall a closed batch waited for one of the in-flight slots
(PIO_SERVE_INFLIGHT) before its device call could be enqueued: gate acquired
- batch closed, over the window's records in the program's serving account.
Back-pressure from the device and the completion thread onto formation."""

from benchmark.lib import account


def read(ctx):
    return account.mean_ms(account.window_dispatches(ctx),
                           "t_gate", "t_closed")
