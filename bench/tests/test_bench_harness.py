"""The harness's contract on the CPU: every cell resolves its files by
name, names and units keep to the allowed characters, the configuration
files are what the program runs, and without a TPU the command prints no
result."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_cells import ROOT

from bench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
  assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
  assert BENCH["command"] == ["python3", "bench/run.py"]
  assert BENCH["paths"] == ["bench"]
  assert 1 <= BENCH["run_seconds"] <= 51
  assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_and_units():
  metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
  configs = [c["name"] for c in BENCH["configs"]]
  for group in (metrics, CELLS, configs):
    assert len(set(group)) == len(group)
  pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
  assert len(set(pairs)) == len(pairs)
  for n in metrics + CELLS + configs + [t for _, t in pairs]:
    assert NAME.match(n), n
  for m in BENCH["end_to_end"] + BENCH["per_layer"]:
    assert UNIT.match(m["unit"]), m
    assert m["better"] in ("lower", "higher")
  for m in BENCH["end_to_end"]:
    assert 0.01 <= m["bound"] <= 0.25
    assert m["source"] in ("host_clock", "device_trace")
  e2e = {m["name"] for m in BENCH["end_to_end"]}
  assert "setup_s" in e2e
  for m in BENCH["per_layer"]:
    assert m["moves"] in e2e
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files_by_name(name):
  cell = harness.resolve(BENCH, name)
  assert cell.chips in (1, 4)
  assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "points_per_s"}
  assert cell.per_layer
  for m in cell.end_to_end + cell.per_layer:
    assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                       m["name"] + ".py"))
  assert set(cell.limits) == {"ids_differ", "value_rel_gap"}
  assert hasattr(cell.driver, "Driver") and hasattr(cell.driver, "reference")
  assert set(cell.traffic["reducers"]) and cell.traffic["reference_sweeps"] >= 1


def test_peaks_are_keyed_by_device_kind():
  from bench import chip
  v5e = chip.peaks("TPU v5 lite")
  assert v5e["bf16_flop_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
  assert "Google Cloud" in v5e["source"]
  with pytest.raises(chip.NoChip):
    chip.peaks("TPU v0 unknown")


def test_sweep_seeds():
  seeds = [harness.sweep_seed(2**31 + 5, i) for i in range(-2, 50)]
  assert len(set(seeds)) == len(seeds)
  assert all(0 <= s < 2**31 for s in seeds)
  assert seeds == [harness.sweep_seed(2**31 + 5, i) for i in range(-2, 50)]


def test_schedule_of_a_fixed_pool():
  traffic = {"questions": 48}
  runs = [harness.schedule(seed, traffic) for seed in (1, 2**31 + 7)]
  asked = [[run(i) for i in range(48)] for run in runs]
  assert asked[0] == asked[1] and len(set(asked[0])) == 48
  for run in runs:  # never a question twice
    with pytest.raises(harness.QuestionsSpent):
      run(48)
  assert not {harness.sweep_seed(s, -1) for s in (1, 2**31 + 7)} & \
      set(asked[0])
  fresh = harness.schedule(1, {})
  assert [fresh(i) for i in range(3)] == [harness.sweep_seed(1, i)
                                          for i in range(3)]


def test_configs_are_what_the_program_runs():
  from repro.core.cnn import SEARCH_SPACE, ArchChoice
  from repro.core.pe import PAPER_PE_TYPES, PE_TYPES
  from repro.core.ppa import HW_RANGES
  from repro.core.supernet import arch_to_layers
  from repro.core.workloads import get_network
  from bench.reference import oracle
  for c in BENCH["configs"]:
    config = harness.load_json(ROOT, c["file"])
    assert config["reduced"] == c["reduced"]
    assert tuple(config["pe_types"]) == PAPER_PE_TYPES
    assert {k: tuple(v) for k, v in config["hw_ranges"].items()} == \
        {k: tuple(v) for k, v in HW_RANGES.items()}
  for name, (act, wgt, psum, gates, mac, crit) in oracle.PE_TYPES.items():
    pe = PE_TYPES[name]
    assert (pe.act_bits, pe.weight_bits, pe.psum_bits, pe.arith_gates,
            pe.mac_energy_pj, pe.critical_path_ns) == \
        (act, wgt, psum, gates, mac, crit)
  # He et al.'s option A: the program's ResNet-20 without its projection
  # convolutions, and the fully connected layer: 6n+2 = 20 weighted layers
  res = harness.resolve(BENCH, "resnet20_cifar10.dse1m")
  assert res.workload["layers"][0] == [
      tuple(int(v) for v in l.features()) for l in get_network("resnet20")
      if not l.name.endswith("proj")] + [(1, 64, 10, 1, 1, 0, 0, 0)]
  assert len(res.workload["layers"][0]) == 20
  joint = harness.resolve(BENCH, "supernet_coexplore.stream10m")
  assert [tuple(map(tuple, s)) for s in joint.config["search_space"]] == \
      [tuple(map(tuple, s)) for s in SEARCH_SPACE]
  assert len(joint.workload["archs"]) == 1000
  for stages, layers in list(zip(joint.workload["archs"],
                                 joint.workload["layers"]))[:50]:
    assert layers == [tuple(int(v) for v in l.features())
                      for l in arch_to_layers(ArchChoice(stages),
                                              image_size=32)]


def _run(args, cwd, env):
  return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                        env=env, capture_output=True, text=True, timeout=240)


def _env():
  env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
  env["JAX_PLATFORMS"] = "cpu"
  return env


def test_no_tpu_no_result():
  p = _run(["--workload", CELLS[0], "--seed", str(2**31 + 3), "--seconds",
            "5", "--trace", "0"], ROOT, _env())
  assert p.returncode == 3, p.stderr[-2000:]
  assert "no TPU" in p.stderr
  assert not p.stdout.strip()


def test_benchmark_alone_gives_no_result(tmp_path):
  shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
  shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                  ignore=shutil.ignore_patterns("__pycache__"))
  p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "5"],
           tmp_path, _env())
  assert p.returncode != 0
  assert not p.stdout.strip()
