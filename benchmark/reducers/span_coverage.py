"""Share of the ``root`` span's time that its leaf spans account for, over
the traced slice's ticks, times ``scale``."""

from benchmark import program_spans


def reduce(facts, args):
    ticks = program_spans.slice_ticks()
    if ticks is None:
        return None
    share = program_spans.coverage(ticks, args.get("root", program_spans.ROOT))
    return None if share is None else share * float(args.get("scale", 1.0))
