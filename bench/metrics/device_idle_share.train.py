"""device_idle_share.train: 1 − (union of op intervals on each device) /
traced window, as a percentage, averaged over the cell's devices."""
from bench import trace as tr


def read(ctx):
    if not ctx["trace"].devices:
        return None
    return 100.0 * tr.idle_share(ctx["trace"])
