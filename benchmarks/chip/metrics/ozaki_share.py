"""Share of the device's busy time spent in offloaded GEMM sites, in percent.

The innermost op time of every ``ozaki_<site>`` scope inside the traced
window (``scope_reduce``), over the device's busy time there.  Nothing
without a device trace, or where no op carries an ``ozaki_`` scope.
"""

import reader_context


def read(ctx):
    scopes = reader_context.scopes(ctx)
    if scopes is None or scopes["busy_s"] <= 0:
        return None
    ozaki = scopes["scoped_s"]["ozaki"]
    if ozaki <= 0:
        return None
    return 100.0 * ozaki / scopes["busy_s"]
