"""The benchmark of the hosted-pool tick (BENCHMARK.json at the repo root).

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, spans, the trace reduction, the table
of peaks, the roofline's byte count, each configuration's plain reference
and the comparison that decides ``correct``.
"""
