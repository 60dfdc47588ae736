"""Per-layer metric arithmetic: ``reduce(facts, args)`` returns a number, or
None where the run holds nothing for it to read."""
