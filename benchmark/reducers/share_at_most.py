"""Percent of a series' samples that are at most ``limit``."""


def reduce(facts, args):
    series = facts["series"].get(args["series"])
    if not series:
        return None
    limit = float(args["limit"])
    return 100.0 * sum(v <= limit for v in series) / len(series)
