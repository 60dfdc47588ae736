"""A named gauge of the program's default registry
(``ggrs_tpu.obs.registry``), times ``scale``.  A program without the gauge
(the parent of the PR that brought it) or one that never set it reads as
nothing, never as 0."""

from ggrs_tpu.obs.registry import default_registry


def reduce(facts, args):
    value = default_registry().value(args["gauge"])
    if not value:
        return None
    return float(value) * float(args.get("scale", 1.0))
