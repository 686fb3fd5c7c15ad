"""How unevenly the routing loads the held experts: from the program's
``moe_load`` counter over the window, each layer's busiest held expert's
assignments over the layer's mean, averaged over layers (1 is even)."""

import numpy as np


def read(ctx):
    load = ctx["readings"].get("moe_load")
    if load is None:
        return None
    load = np.asarray(load, np.float64)
    mean = load.mean(axis=1)
    if not (mean > 0).all():
        return None
    return float((load.max(axis=1) / mean).mean())
