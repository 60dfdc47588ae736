"""A count of the run as it stands, times ``scale``."""


def reduce(facts, args):
    value = facts["counts"].get(args["count"])
    if value is None:
        return None
    return float(value) * float(args.get("scale", 1.0))
