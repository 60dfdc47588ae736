"""Share of the HBM roofline: the bytes the plan needs (``bytes`` count)
over the chip's peak bandwidth, over the device seconds it took (``seconds``
count).  Without a device trace there is no time to divide by: no value,
never 0."""

from benchmark import roofline


def reduce(facts, args):
    moved = facts["counts"].get(args["bytes"])
    seconds = facts["counts"].get(args["seconds"])
    if not moved or not seconds:
        return None
    return roofline.roofline_share(moved, seconds, facts["peaks"])
