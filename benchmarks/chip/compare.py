"""The numbers that decide `correct`, each beside its limit.

Training (every cell follows its first three steps with the plain
reference):

* `loss_gap`: the largest |program loss - reference loss| over the
  steps, in nats.
* `change_gap`: over the leaves, the largest gap between the norm of
  each leaf's change over the steps in the program and in the
  reference, as a share of the larger of the reference leaf's norm and
  the median leaf's.  Leaves whose reference gradient is under a
  thousandth of the median leaf's (the key bias, whose gradient softmax
  cancels) move by round-off alone and are left out.

Read but not compared (PERF.md gives why): `grad_gap`, the same worst-
leaf gap for the norm of the first gradient as the optimizer got it
(AdamW's first moment after one step over 1 - beta1), and
`grad_gap_median`, the median leaf's gap.

Bytes read back (exact, limit 0):

* `image_mismatch`: leaves of the newest image, read back through the
  program's restore, whose fingerprint differs from the state at its
  step.
* `restore_mismatch`: over every resume, leaves of the restored state
  that differ from the state that was saved, plus resumes that started
  from another step.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

NOUGHT_SHARE = 1e-3


def _worst(prog: Dict[str, float], ref: Dict[str, float],
           keep=None) -> Tuple[float, str]:
    med = statistics.median(ref.values())
    worst, leaf = 0.0, ""
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= NOUGHT_SHARE * med]


def training(prog: Dict, ref: Dict) -> Dict[str, Dict]:
    """prog/ref: {"losses": [...], "grad_norms": {...},
    "change_norms": {...}} -> {number: {"value", "detail"}}."""
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        gaps.append(float("inf"))
    moved = moved_leaves(ref["grad_norms"])
    change, cleaf = _worst(prog["change_norms"], ref["change_norms"],
                           keep=set(moved))
    return {
        "loss_gap": {"value": max(gaps), "detail": "max over steps"},
        "change_gap": {"value": change, "detail": cleaf},
    }


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Numbers read beside the checks, not compared."""
    grad, gleaf = _worst(prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    gaps = [abs(prog["grad_norms"][k] - r) / max(r, med, 1e-30)
            for k, r in ref["grad_norms"].items()]
    return {"grad_gap": grad, "grad_gap_leaf": gleaf,
            "grad_gap_median": statistics.median(gaps)}


def judge(numbers: Dict[str, Dict], limits: Dict[str, Optional[float]]
          ) -> Tuple[bool, Dict[str, Dict]]:
    """Each number beside its limit; correct when every one is within."""
    out, ok = {}, True
    for name, n in numbers.items():
        lim = limits.get(name)
        good = lim is not None and n["value"] <= lim
        ok = ok and good
        out[name] = {"value": n["value"], "limit": lim}
    return ok, out
