"""The offloaded GEMMs' share of their roofline, in percent.

The least time the window's offloaded products need, their work
(``gemm_counts``: 2 m k n of every product the step offloads, times the
window's steps) at the chip's int8 peak, over the device time the
trace spends in ``ozaki_<site>`` scopes (``scope_reduce``).  The int8
rate is the fastest any engine runs a matrix product at on this chip,
so the share cannot pass 100%.  Nothing without the chip's peaks, a
device trace, or ops that carry an ``ozaki_`` scope.
"""

import gemm_counts
import reader_context


def read(ctx):
    peaks, work = ctx["peaks"], ctx["work"]
    if peaks is None or not work.get("steps"):
        return None
    scopes = reader_context.scopes(ctx)
    cell = reader_context.running_cell()
    if scopes is None or cell is None:
        return None
    ozaki = scopes["scoped_s"]["ozaki"]
    if ozaki <= 0:
        return None
    ops = work["steps"] * gemm_counts.lm_train_offloaded_ops(*cell)
    return 100.0 * ops / peaks["int8_ops_per_s"] / ozaki
