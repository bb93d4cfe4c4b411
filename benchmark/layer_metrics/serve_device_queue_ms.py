"""What of a dispatch's turnaround is not its own device time:
serve_turnaround_ms less the device milliseconds per run of the batched
top-k executables in the trace. At steady state, the wait behind the one or
two programs enqueued ahead of it (plus the copy back and the completion
thread's pickup, which serve_stage_ms.readback and serve_d2h_wait_ms
bound)."""

from benchmark.lib import account


def read(ctx):
    turnaround = account.mean_ms(account.window_dispatches(ctx),
                                 "t_ready", "t_begin")
    device = account.device_ms_per_dispatch(ctx)
    if turnaround is None or device is None:
        return None
    return turnaround - device
