"""Share of the device's busy time spent dispatching and combining
routed rows, in percent.

The innermost op time of the ``moe_dispatch`` (sort by expert, row
gather) and ``moe_combine`` (weighted scatter back) scopes inside the
traced window, backward ops included (``moe_scopes``), over the
device's busy time there.  Nothing without a device trace, or where no
op carries those scopes.
"""

import moe_scopes


def read(ctx):
    found = moe_scopes.parts(ctx)
    if found is None or found["busy_s"] <= 0:
        return None
    moved = (found["parts_s"]["moe_dispatch"]
             + found["parts_s"]["moe_combine"])
    if moved <= 0:
        return None
    return 100.0 * moved / found["busy_s"]
