"""The ``q``-th percentile over ALL samples of a series (linear between
order statistics, as ``numpy.percentile``); an empty series has none."""

import numpy as np


def reduce(facts, args):
    series = facts["series"].get(args["series"])
    if not series:
        return None
    return float(np.percentile(np.asarray(series, float), float(args["q"])))
