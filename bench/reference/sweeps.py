"""Plain reference of the two sweeps the benchmark drives, and of the
fronts they fold: which design points are sampled for a seed, their
metrics (:mod:`bench.reference.oracle`), and the exact Pareto fronts and
top-k lists over every point, each point named by its global row id.

Row ids follow the sweep's documented order: PE type by PE type, in the
configuration's order; a plain sweep's rows in sample order within a
type, a joint sweep's rows arch-major (``arch * n_hw + hw``) within a
type.
"""
from __future__ import annotations

import numpy as np

from bench.reference import oracle

# columns that are better when larger (negated before minimizing)
MAXIMIZE = frozenset({"perf", "perf_per_area", "top1"})


def sample_hw(space: dict, n: int, seed: int) -> dict:
  """n design points of one PE type: each knob drawn uniformly from its
  values by its own RandomState stream, seeded (seed mod 2**32, knob
  salt)."""
  out = {}
  for ai, knob in enumerate(oracle.KNOBS):
    vals = np.asarray(space[knob])
    rng = np.random.RandomState(
        np.asarray([seed % (2 ** 32), 0x9E3779B9 ^ ai], np.uint32))
    out[knob] = vals[rng.randint(0, len(vals), size=n)]
  return out


def network_latency(c: dict, layers, clock) -> np.ndarray:
  """Seconds per design point for one network: cycles summed layer by
  layer in order, over the clock."""
  total = 0.0
  for layer in layers:
    total = total + oracle.layer_cycles(c, layer, clock)
  return total / (clock * 1e6)


def columns_of(lat, pwr, area, top1=None) -> dict:
  perf = 1.0 / np.maximum(lat, 1e-12)
  cols = {"latency_s": lat, "power_mw": pwr, "area_mm2": area,
          "perf": perf, "perf_per_area": perf / np.maximum(area, 1e-12),
          "energy_mj": pwr * lat}
  if top1 is not None:
    cols["top1"] = top1
    cols["top1_err"] = 1.0 - top1
  return cols


def front_2d(x: np.ndarray, y: np.ndarray) -> np.ndarray:
  """Mask of the points no other point dominates (both minimized): after
  sorting by x then y, a point stays when its y is below every y of a
  strictly smaller x and is the least y of its own x."""
  n = x.shape[0]
  order = np.lexsort((y, x))
  xs, ys = x[order], y[order]
  first = np.ones(n, bool)
  first[1:] = xs[1:] != xs[:-1]
  starts = np.flatnonzero(first)
  group = np.cumsum(first) - 1
  prefix = np.minimum.accumulate(ys)
  before = np.full(starts.shape, np.inf)
  before[1:] = prefix[starts[1:] - 1]
  keep = (ys < before[group]) & (ys == ys[starts][group])
  mask = np.zeros(n, bool)
  mask[order] = keep
  return mask


def front_nd(obj: np.ndarray, block: int = 512) -> np.ndarray:
  """Mask of the rows of ``obj`` (minimized) no other row dominates, by
  comparing every row with every other, a block at a time."""
  n = obj.shape[0]
  mask = np.ones(n, bool)
  for lo in range(0, n, block):
    p = obj[lo:lo + block, None, :]
    dom = np.all(obj[None] <= p, axis=2) & np.any(obj[None] < p, axis=2)
    mask[lo:lo + block] = ~dom.any(axis=1)
  return mask


def objectives(cols: dict, names) -> np.ndarray:
  return np.stack([-cols[c] if c in MAXIMIZE else cols[c] for c in names],
                  axis=1)


def top_k(key: np.ndarray, ids: np.ndarray, k: int) -> np.ndarray:
  """Positions of the k smallest keys, ties by the smaller row id,
  best first."""
  k = min(k, key.shape[0])
  thresh = np.partition(key, k - 1)[k - 1]
  sel = np.flatnonzero(key <= thresh)
  return sel[np.lexsort((ids[sel], key[sel]))][:k]


def _result(ids, cols, pos) -> dict:
  return {"ids": ids[pos], **{c: cols[c][pos]
                              for c in ("latency_s", "power_mw", "area_mm2")}}


def reduce_points(ids, cols, reducers: dict) -> dict:
  """Every reducer's exact answer over the given points: a Pareto front
  in ascending row id, or a top-k list best first."""
  out = {}
  for name, spec in reducers.items():
    if spec["kind"] == "pareto":
      obj = objectives(cols, spec["cols"])
      pos = np.flatnonzero(front_2d(obj[:, 0], obj[:, 1])
                           if obj.shape[1] == 2 else front_nd(obj))
      pos = pos[np.argsort(ids[pos])]
    else:
      key = cols[spec["by"]]
      pos = top_k(-key if spec["by"] in MAXIMIZE else key, ids, spec["k"])
    out[name] = _result(ids, cols, pos)
  return out


def explore(config: dict, layers, n_per_type: int, seed: int,
            reducers: dict) -> dict:
  """Plain sweep: ``n_per_type`` random points of each PE type (type i
  seeded ``seed + 100 * i``), each evaluated on one network."""
  ids, parts = [], []
  for ti, pe_type in enumerate(config["pe_types"]):
    c = oracle.columns(pe_type, sample_hw(config["hw_ranges"], n_per_type,
                                          seed + 100 * ti))
    clock, pwr, area = oracle.hw_targets(c)
    parts.append((network_latency(c, layers, clock), pwr, area))
    ids.append(ti * n_per_type + np.arange(n_per_type, dtype=np.int64))
  lat, pwr, area = (np.concatenate(x) for x in zip(*parts))
  return reduce_points(np.concatenate(ids), columns_of(lat, pwr, area),
                       reducers)


def co_explore(config: dict, arch_layers, accs, n_hw_per_type: int,
               seed: int, reducers: dict) -> dict:
  """Joint sweep: ``n_hw_per_type`` random points of each PE type (type i
  seeded ``seed + 17 * i``) crossed with every architecture.

  A point on a front whose objectives include an architecture-constant
  column (``top1_err``) must lie on its architecture's front over the
  other columns, so fronts are found per architecture first and then
  among those candidates."""
  n_archs, n_hw = len(arch_layers), n_hw_per_type
  distinct = sorted({tuple(l) for ls in arch_layers for l in ls})
  where = {l: i for i, l in enumerate(distinct)}
  per_type = []
  for ti, pe_type in enumerate(config["pe_types"]):
    c = oracle.columns(pe_type, sample_hw(config["hw_ranges"], n_hw,
                                          seed + 17 * ti))
    clock, pwr, area = oracle.hw_targets(c)
    cyc = np.stack([oracle.layer_cycles(c, l, clock) for l in distinct])
    lat = np.empty((n_archs, n_hw))
    for a, ls in enumerate(arch_layers):
      total = 0.0
      for l in ls:
        total = total + cyc[where[tuple(l)]]
      lat[a] = total / (clock * 1e6)
    per_type.append((lat, pwr, area))
  accs = np.asarray(accs, np.float64)
  # (type, arch, hw) grids, flattened per arch as (type, hw)
  lat = np.stack([t[0] for t in per_type])
  pwr = np.broadcast_to(np.stack([t[1] for t in per_type])[:, None, :],
                        lat.shape)
  area = np.broadcast_to(np.stack([t[2] for t in per_type])[:, None, :],
                         lat.shape)
  top1 = np.broadcast_to(accs[None, :, None], lat.shape)
  ids = (np.arange(len(per_type))[:, None, None] * (n_archs * n_hw)
         + np.arange(n_archs)[None, :, None] * n_hw
         + np.arange(n_hw)[None, None, :])
  flat = lambda g: np.ascontiguousarray(g).reshape(-1)  # noqa: E731
  cols = columns_of(flat(lat), flat(pwr), flat(area), flat(top1))
  ids = flat(ids)
  out = {}
  for name, spec in reducers.items():
    if spec["kind"] == "pareto" and len(spec["cols"]) > 2 \
        and any(c in ("top1", "top1_err") for c in spec["cols"]):
      rest = [c for c in spec["cols"] if c not in ("top1", "top1_err")]
      obj = objectives(cols, rest).reshape(len(per_type), n_archs, n_hw, -1)
      cand = []
      for a in range(n_archs):
        o = obj[:, a].reshape(-1, obj.shape[-1])
        keep = front_2d(o[:, 0], o[:, 1]) if o.shape[1] == 2 else front_nd(o)
        t, h = np.divmod(np.flatnonzero(keep), n_hw)
        cand.append(t * (n_archs * n_hw) + a * n_hw + h)
      cand = np.concatenate(cand)
      sub = reduce_points(ids[cand], {k: v[cand] for k, v in cols.items()},
                          {name: spec})
      out[name] = sub[name]
    else:
      out.update(reduce_points(ids, cols, {name: spec}))
  return out
