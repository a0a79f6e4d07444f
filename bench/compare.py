"""The comparison that decides ``correct``: what a sweep folded against
what the plain reference says every reducer's answer is.

Two numbers are compared, each against the cell's limit
(``bench/limits/<cell>.json``):

``ids_differ``
    row ids in one answer and not in the other, summed over reducers and
    sweeps; a top-k list counts every position whose id differs.
``value_rel_gap``
    the widest relative gap, ``|program / reference - 1|``, of latency,
    power or area over the rows both answers hold.
"""
from __future__ import annotations

import numpy as np

from bench.sweep import ANSWER_COLUMNS


def readings(program: dict, reference: dict, specs: dict) -> dict:
  """Both numbers for one sweep's answers; ``specs`` are the traffic
  mix's reducers (a ``topk`` answer is a ranked list)."""
  differ, gap = 0, 0.0
  for name, ref in reference.items():
    got = program[name]
    if specs[name]["kind"] == "topk":
      n = min(len(got["ids"]), len(ref["ids"]))
      differ += int(np.count_nonzero(got["ids"][:n] != ref["ids"][:n]))
      differ += abs(len(got["ids"]) - len(ref["ids"]))
    else:
      differ += int(np.setxor1d(got["ids"], ref["ids"]).size)
    common, gi, ri = np.intersect1d(got["ids"], ref["ids"],
                                    return_indices=True)
    for c in ANSWER_COLUMNS:
      if common.size:
        rel = np.abs(got[c][gi] / ref[c][ri] - 1.0)
        gap = max(gap, float(np.max(rel)))
  return {"ids_differ": differ, "value_rel_gap": gap}


def combine(per_sweep: list) -> dict:
  """Sum the id counts and take the widest gap over the compared sweeps."""
  return {"ids_differ": sum(r["ids_differ"] for r in per_sweep),
          "value_rel_gap": max((r["value_rel_gap"] for r in per_sweep),
                               default=0.0)}


def verdict(numbers: dict, limits: dict) -> dict:
  """Each number beside its limit."""
  return {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}


def within(checks: dict) -> bool:
  return all(c["value"] <= c["limit"] for c in checks.values())
