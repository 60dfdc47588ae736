"""One count carried by a span of the program over another of the same
span, summed over the traced slice's ticks, times ``scale``."""

from benchmark import program_spans


def reduce(facts, args):
    ticks = program_spans.slice_ticks()
    if ticks is None:
        return None
    return program_spans.arg_share(
        ticks, args["span"], args["num"], args["den"],
        float(args.get("scale", 1.0)))
