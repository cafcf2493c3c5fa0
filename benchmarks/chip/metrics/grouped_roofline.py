"""The offloaded grouped products' share of their roofline, in percent.

The least time the window's grouped products need, their work
(``moe_counts.grouped_ops``: 2 m k n of every grouped product the step
offloads, its rows at their balanced expectation, times the window's
steps) at the chip's int8 peak, over the device time the trace spends
in the grouped sites' ``ozaki_<site>`` scopes (``moe_scopes``).  Each
product costs ``num_pair_gemms(s)`` int8 pair products, so at s=4 the
share cannot pass 10%.  Nothing without the chip's peaks, a device
trace, or ops in such scopes.
"""

import moe_counts
import moe_scopes
import reader_context


def read(ctx):
    peaks, work = ctx["peaks"], ctx["work"]
    if peaks is None or not work.get("steps"):
        return None
    found = moe_scopes.parts(ctx)
    cell = reader_context.running_cell()
    if found is None or cell is None:
        return None
    grouped = found["parts_s"]["grouped"]
    if grouped <= 0:
        return None
    ops = work["steps"] * moe_counts.grouped_ops(*cell)
    return 100.0 * ops / peaks["int8_ops_per_s"] / grouped
