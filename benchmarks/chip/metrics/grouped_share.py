"""Share of the device's busy time spent in offloaded grouped sites, in
percent.

The innermost op time of every ``ozaki_<site>`` scope of a grouped
(``ragged``) site inside the traced window (``moe_scopes``), over the
device's busy time there.  Nothing without a device trace, or where no
op carries such a scope (a program without grouped sites).
"""

import moe_scopes


def read(ctx):
    found = moe_scopes.parts(ctx)
    if found is None or found["busy_s"] <= 0:
        return None
    grouped = found["parts_s"]["grouped"]
    if grouped <= 0:
        return None
    return 100.0 * grouped / found["busy_s"]
