"""Share of the traced window in which no operation ran on the device.

1 - busy / window, from the trace (``trace_reduce``), in percent.
Nothing without a device trace.
"""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
