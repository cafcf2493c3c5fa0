"""The whole workload's share of the chip's peak, in percent.

The model operations the window completed (``work["model_flops"]``,
counted by ``counts`` from the shapes), per second of the window, over
the chip's int8 peak.  The int8 rate is the fastest any engine runs a
matrix product at on this chip (its float32 products are bf16 or int8
passes), so the share reads the same required work whatever engine runs
it and cannot pass 100%.  Nothing without the chip's peaks.
"""


def read(ctx):
    peaks, work = ctx["peaks"], ctx["work"]
    if peaks is None or "model_flops" not in work or not ctx["window_s"]:
        return None
    return (100.0 * work["model_flops"] / ctx["window_s"]
            / peaks["int8_ops_per_s"])
