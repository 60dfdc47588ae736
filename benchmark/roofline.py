"""What the tick program *needs* to move, from the plan and the
configuration's shapes — never from the implementation.

Per session-tick the algorithm has to read and write the live state once per
frame advanced, write one ring slot per save and read one per load.  The
program is bound by HBM (integer state updates, no matmul worth the name),
so its least time is those bytes over the chip's peak bandwidth.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


COUNTED = ("advances", "saves", "loads")


def plan_counts(plan: Any) -> Dict[str, int]:
    """Advances, saves and loads one tick's ``RequestPlan`` asks for.  A plan
    that states its own tally (``counts``, a mapping of those three) is
    counted by it, so that row kinds this rule does not know (a sparse
    session's advance without a save) need no edit here.  Otherwise they are
    read from its public columns (``quiet_rows`` = [save, advance] each;
    ``resim_rows`` = load, ``n_adv`` advances, a save after each but a
    trailing live one; ``save_only_rows`` = one save; ``eager_rows`` =
    materialized request lists, counted by type)."""
    stated = getattr(plan, "counts", None)
    if stated is not None:
        return {k: int(stated[k]) for k in COUNTED}
    quiet = int(plan.quiet_rows.size) if plan.quiet_rows is not None else 0
    advances, saves, loads = quiet, quiet, 0
    for row in plan.resim_rows:
        n_adv, trailing = int(row[2]), bool(row[3])
        loads += 1
        advances += n_adv
        saves += n_adv - 1 if trailing else n_adv
    saves += len(plan.save_only_rows)
    for b in plan.eager_rows:
        for req in plan.lists[b] or ():
            kind = type(req).__name__
            if kind == "AdvanceFrame":
                advances += 1
            elif kind == "SaveGameState":
                saves += 1
            elif kind == "LoadGameState":
                loads += 1
    return {"advances": advances, "saves": saves, "loads": loads}


def bytes_needed(counts: Dict[str, int], state_bytes: int) -> int:
    """State read + written per advance, one slot written per save, one
    read per load."""
    return state_bytes * (
        2 * counts["advances"] + counts["saves"] + counts["loads"]
    )


def roofline_share(bytes_moved: float, seconds: float,
                   peaks: Dict[str, Any]) -> Optional[float]:
    """Percent of the HBM roofline: least time over measured time."""
    if seconds <= 0 or bytes_moved <= 0:
        return None
    least = bytes_moved / (float(peaks["hbm_gbs"]) * 1e9)
    return 100.0 * least / seconds
