"""The mean of one argument over the spans of one name that carry it, over
the traced slice's ticks, times ``scale`` (``benchmark/program_spans.py``).
A span without the argument counts for nothing, so a program older than the
argument (the parent of the PR that brought it) reads as nothing, never as
0: ``span_arg_share`` would divide by a count such a program already has."""

from benchmark import program_spans


def reduce(facts, args):
    ticks = program_spans.slice_ticks()
    if ticks is None:
        return None
    span, arg = args["span"], args["arg"]
    values = [e[6][arg] for events in ticks.values() for e in events
              if e[1] == span and e[6] and arg in e[6]]
    if not values:
        return None
    return float(args.get("scale", 1.0)) * sum(values) / len(values)
