"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, from the same weights, data and schedule.

The numbers (a workload file's ``check.limits`` names the ones its cell
compares, each with its limit):

* ``loss1``   - round 1's local loss (the clients' mean over their local
  steps, from the same weights and rows on both sides), relative gap;
* ``loss``    - the worst round's local loss, relative gap;
* ``weights1``, ``weights`` - round 1's / the worst round's L1 distance of
  the aggregation weights;
* ``scores``  - the worst round's largest gap of a client's score;
* ``scores1`` - round 1's largest gap of a client's score (in round 1 a
  score is the client's mean accuracy over the testers' rows);
* ``scores1_moved`` - the share of clients whose round-1 score moved:
  whose gap exceeds ``MOVED`` (rounding; one row of a tester is
  1 / (testers x rows));
* ``update1`` - the global model's change over round 1, and
  ``updateN`` - its change over all compared rounds: the worst leaf's gap
  between the two norms of the change, against the larger of that leaf's
  reference norm and the median leaf's. Leaves whose reference change is
  under a thousandth of the median leaf's (gradients that are nought to
  rounding, such as a key bias under softmax) are left out.

Rounds after the first start from global models that already differ
where a tester's accuracy flipped on one row, so their numbers swing from
seed to seed. Within round 1 a rounding difference moves few clients:
their ten SGD steps amplify it only where a ReLU or a max-pool switches,
so a sound run moves one to a few clients and a lower precision most of
them. ``scores1_moved`` counts them, and no one client sets it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

EXCLUDE_BELOW = 1e-3
MOVED = 1e-6


def leaf_gap(prog: List[float], ref: List[float]) -> Dict[str, float]:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref))
    keep = ref >= EXCLUDE_BELOW * med
    denom = np.maximum(ref, med)
    gaps = np.abs(prog - ref)[keep] / np.maximum(denom[keep], 1e-30)
    return {"gap": float(gaps.max()) if gaps.size else 0.0,
            "excluded": int((~keep).sum())}


def score_gaps1(prog: dict, ref: dict) -> np.ndarray:
    """Each client's round-1 score gap."""
    return np.abs(np.asarray(prog["scores"][0], np.float64)
                  - np.asarray(ref["scores"][0], np.float64))


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref``: per-round lists ``local_loss``, ``weights``,
    ``scores`` and the per-leaf change norms ``update1``, ``updateN``."""
    rounds = range(len(ref["local_loss"]))
    loss = [abs(prog["local_loss"][r] - ref["local_loss"][r])
            / max(abs(ref["local_loss"][r]), 1e-12) for r in rounds]
    weights = [float(np.abs(np.asarray(prog["weights"][r])
                            - np.asarray(ref["weights"][r])).sum())
               for r in rounds]
    out = {
        "loss1": loss[0], "loss": max(loss),
        "weights1": weights[0], "weights": max(weights),
        "scores": max(float(np.abs(np.asarray(prog["scores"][r])
                                   - np.asarray(ref["scores"][r])).max())
                      for r in rounds),
    }
    gaps1 = score_gaps1(prog, ref)
    out["scores1"] = float(gaps1.max())
    out["scores1_moved"] = float(np.mean(gaps1 > MOVED))
    for key in ("update1", "updateN"):
        out[key] = leaf_gap(prog[key], ref[key])["gap"]
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, dict]:
    """Each compared number (those ``limits`` names) beside its limit; a
    number that is not finite fails, and no limits at all fail."""
    if not limits:
        return {"limits": {"value": float("nan"), "limit": None,
                           "ok": False}}
    return {k: {"value": float(values[k]), "limit": lim,
                "ok": bool(np.isfinite(values[k]) and values[k] <= lim)}
            for k, lim in limits.items()}
