"""One count over another, times ``scale``; with ``complement`` one minus
the ratio.  Nothing to divide by, or nothing counted: no value."""


def reduce(facts, args):
    num = facts["counts"].get(args["num"])
    den = facts["counts"].get(args["den"])
    if num is None or not den:
        return None
    share = float(num) / float(den)
    if args.get("complement"):
        share = 1.0 - share
    return share * float(args.get("scale", 1.0))
