"""QUIDAM quickstart via the unified repro.explore API: fit PPA models
once, explore the design space, print the paper's headline comparison
(LightPE vs INT16), then rerun the sweep through the vectorized exact
oracle (ConfigTable + VectorOracleBackend) — all in under a minute.

Run: PYTHONPATH=src python examples/quickstart.py
Env: QUICKSTART_JIT=1        enable the approximate jax.jit device path
     QUICKSTART_CHUNK=65536  vector backend chunk size (bounded memory)
"""
import os
import time

from repro.compile_cache import enable_compile_cache
from repro.core.workloads import get_network
from repro.explore import (DesignSpace, ExplorationSession,
                           PolynomialBackend, VectorOracleBackend)


def main():
  enable_compile_cache()
  layers = get_network("resnet20")
  space = DesignSpace()
  print(f"design space: {space!r}")

  # --- the paper's fast path: fit-once polynomial models -------------------
  print("Fitting power/area/latency polynomial models (4 PE types)...")
  backend = PolynomialBackend.fit(degree=5, n_train=200, layers=layers)
  session = ExplorationSession(backend, space)
  frame = session.explore(layers, "resnet20", n_per_type=200,
                          measure_oracle=3)
  ppa_n, en_n = frame.normalize(ref="best-int16")
  print(f"\n{len(frame)} design points (ResNet-20), normalized to the "
        "best INT16 configuration:")
  print(f"{'PE type':12s} {'best perf/area':>15s} {'best energy':>12s}")
  for t in ("FP32", "INT16", "LightPE-2", "LightPE-1"):
    m = frame.by_type(t)
    print(f"{t:12s} {ppa_n[m].max():14.2f}x {en_n[m].min():11.3f}x")
  print(f"\nmodel eval: {frame.meta['eval_us_per_design']:.0f} "
        f"us/design vs oracle "
        f"{frame.meta['oracle_seconds_per_design'] * 1e3:.1f} "
        "ms/design (vs hours for real synthesis)")
  best = frame.top_k(1, by="perf_per_area")
  print(f"best design: {best.config_at(0)}")

  # --- the vectorized exact path: ConfigTable + VectorOracleBackend --------
  # Same oracle, array-at-a-time: a struct-of-arrays ConfigTable flows
  # through the *_batch formulas in bounded-memory chunks.  Bit-identical
  # to OracleBackend on the numpy path; QUICKSTART_JIT=1 switches the
  # per-chunk formulas to jax.jit (float32-approximate, throughput only).
  chunk = int(os.environ.get("QUICKSTART_CHUNK", "65536"))
  use_jit = os.environ.get("QUICKSTART_JIT", "0") == "1"
  n_per_type = 25_000  # 100k exact characterizations in ~a second
  vec = VectorOracleBackend(chunk_size=chunk, jit=use_jit)
  vsession = ExplorationSession(vec, space)
  t0 = time.perf_counter()
  vframe = vsession.explore(layers[:4], "resnet20-head",
                            n_per_type=n_per_type)
  dt = time.perf_counter() - t0
  print(f"\nvectorized exact oracle: {len(vframe):,} design points in "
        f"{dt:.2f}s ({len(vframe) / dt:,.0f} pts/s; chunk={chunk}, "
        f"jit={'on' if use_jit else 'off'})")
  front = vframe.pareto(cols=("perf_per_area", "energy_mj"))
  print(f"pareto front: {int(front.sum())} of {len(vframe):,} points; "
        f"best exact design: {vframe.top_k(1, by='perf_per_area').config_at(0)}")


if __name__ == "__main__":
  main()
