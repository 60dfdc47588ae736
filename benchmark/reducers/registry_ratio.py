"""One counter of the program's default registry (``ggrs_tpu.obs.registry``)
over another, times ``scale``.  A program without either counter (the parent
of the PR that brought them), or a run that counted nothing under either,
reads as nothing, never as 0."""

from ggrs_tpu.obs.registry import default_registry


def reduce(facts, args):
    registry = default_registry()
    num, den = registry.value(args["num"]), registry.value(args["den"])
    if not num or not den:
        return None
    return float(args.get("scale", 1.0)) * float(num) / float(den)
