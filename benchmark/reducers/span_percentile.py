"""The ``q``-th percentile over the traced slice's ticks of the program's
own spans: per tick the ``add`` spans' time less the ``sub`` spans', in ms
(``benchmark/program_spans.py``).  No slice, no tracer: no value."""

from benchmark import program_spans


def reduce(facts, args):
    ticks = program_spans.slice_ticks()
    if ticks is None:
        return None
    return program_spans.percentile_ms(
        ticks, args["add"], args.get("sub", ()), args["q"])
