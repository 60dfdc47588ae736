#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

This system runs no model and states no precision, so the control breaks one
guarantee the configuration states — *every peer bit-identical to the true
simulation*.  It is the plain reference put in the program's place: every
match is replayed at the cell's own size over the true inputs, but the
match's last peer never corrects ONE misprediction (at a frame drawn from
the seed at which player 0's input really changed, it keeps the repeat-last
input — a rollback that was skipped, the step that would tempt a later PR).
The same comparison as a run's then has to count sessions that differ.

    python3 benchmark/control.py --workload <name> --seed <n> --ticks <t>

No benchmark run calls this; ``tests/benchmark`` keeps it at a small size.
"""

from __future__ import annotations

import argparse
import importlib
import json
import random
import sys
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import generator  # noqa: E402


def _replay(ref: Any, config: Dict[str, Any], rows: np.ndarray) -> Dict[str, np.ndarray]:
    state = ref.init_state(config, rows.shape[1])
    for row in rows:
        state = ref.advance(config, state, row)
    return state


def control_checks(config: Dict[str, Any], rows: np.ndarray, seed: int) -> Dict[str, int]:
    """``rows[tick, match, player]`` is what the players press, held at the
    end as a run holds it.  Returns the comparison's numbers for the control."""
    from benchmark.run import wrong_sessions

    ref = importlib.import_module(f"benchmark.reference.{config['adapter']}")
    rows = generator.frame_inputs(rows, int(config["input_delay"]))
    frames, matches, players = rows.shape
    rng = random.Random(seed)
    skipped = rows.copy()
    for m in range(matches):
        changed = [f for f in range(1, frames) if rows[f, m, 0] != rows[f - 1, m, 0]]
        f = rng.choice(changed)
        skipped[f, m, 0] = rows[f - 1, m, 0]
    true, stale = _replay(ref, config, rows), _replay(ref, config, skipped)
    last = np.arange(matches * players) % players == players - 1
    live = {}
    for k in true:
        per_session = np.repeat(true[k], players, axis=0)
        per_session[last] = stale[k]
        live[k] = per_session
    wrong = wrong_sessions(live, true, players)
    return {"state_mismatch_sessions": int(wrong.sum())}


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ticks", type=int, required=True)
    args = ap.parse_args(argv)
    from benchmark.run import load_cell

    spec = load_cell(REPO, args.workload)
    config, traffic = spec["config"], spec["traffic"]
    matches = int(spec["size"]["matches"])
    hold = int(traffic["hold_windows"]) * int(config["max_prediction"])
    rows = generator.schedule(traffic, args.seed, matches, int(config["players"]),
                              args.ticks)
    rows = np.concatenate([rows, np.repeat(rows[-1:], hold, axis=0)])
    checks = control_checks(config, rows, args.seed)
    correct = all(v == 0 for v in checks.values())
    print(json.dumps({"control": "one_rollback_skipped", "workload": args.workload,
                      "seed": args.seed, "correct": correct,
                      "checks": {k: {"value": v, "limit": 0}
                                 for k, v in checks.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
