"""Share of the window in which no operation ran on the device: one minus
the union of the device's operation intervals over the window, averaged
over the chips used (``bench/trace.py``)."""
from bench import trace


def read(ctx):
    busy, window = trace.busy_and_window_s(ctx.trace)
    return 100.0 * (1.0 - busy / window)
