"""Share of the slice's wall that the formation thread spent blocked in
costmon.device_timed's sampled block_until_ready (`pio.device_sync`, one
dispatch in PIO_DEVICE_SYNC_EVERY): the seconds each of the window's
dispatches notes in the program's serving account, summed, over the slice."""

from benchmark.lib import account


def read(ctx):
    recs = account.window_dispatches(ctx)
    if not recs:
        return None
    return 100.0 * sum(r["sync_s"] for r in recs) / ctx["window"]["wall_s"]
