"""(p75 - p25) / p50 of a series, times ``scale``: how widely its samples
lie around their median (quartiles linear between order statistics, as
``numpy.percentile``).  Under four samples, or a median of 0: no value."""

import numpy as np


def reduce(facts, args):
    series = facts["series"].get(args["series"])
    if not series or len(series) < 4:
        return None
    p25, p50, p75 = np.percentile(np.asarray(series, float), [25, 50, 75])
    if not p50:
        return None
    return float(p75 - p25) / float(p50) * float(args.get("scale", 1.0))
