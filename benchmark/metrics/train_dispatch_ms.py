"""Median host time of one step's enqueue (input put and step call)."""

import numpy as np


def read(out):
    t = out.get("train")
    if not t or not t["dispatch_s"]:
        return None
    return 1e3 * float(np.median(t["dispatch_s"]))
