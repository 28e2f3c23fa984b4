"""Share (%) of the routed expert entries of real tokens that fall on
the experts held on this chip: the program's ``moe_local`` over
``moe_routed``, over the window. Under uniform routing it is the held
share of the router's experts (8 of 64: 12.5%)."""

import metrics_common as mc


def read(record):
    local = mc.program_counter_delta(record, "moe_local")
    routed = mc.program_counter_delta(record, "moe_routed")
    if local is None or not routed:
        return None
    return 100.0 * local / routed
