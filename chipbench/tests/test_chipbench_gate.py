"""No chip, no result; a cell is added by files and entries alone."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as bench

ROOT = bench.ROOT
CELL = "sim-fit.squeezenet1_1.table1"


def run_cli(cwd, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--workload",
         CELL, "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_unknown_device_kind_raises():
    with pytest.raises(SystemExit) as e:
        bench.peaks_for("TPU v99 imaginary")
    assert e.value.code != 0
    assert bench.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_run_without_tpu_exits_nonzero_with_no_result():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def copy_benchmark(dst):
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("out", ".cache", "tests",
                                                  "__pycache__"))


def test_benchmark_alone_exits_nonzero(tmp_path):
    copy_benchmark(tmp_path)
    p = run_cli(tmp_path, root=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def digests(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_config_traffic_and_metric_by_files_alone(tmp_path):
    copy_benchmark(tmp_path)
    bench_dir = tmp_path / "chipbench"
    before = digests(bench_dir)
    config = json.loads((bench_dir / "configs" /
                         "squeezenet1_1.table1.json").read_text())
    config.update(name="squeezenet1_1.c12", clinics=12)
    (bench_dir / "configs" / "squeezenet1_1.c12.json").write_text(
        json.dumps(config))
    traffic = json.loads((bench_dir / "traffic" /
                          "trainer-fit.json").read_text())
    traffic.update(check_calls=5, checked_changes=[1, 2])
    (bench_dir / "traffic" / "trainer-fit-5.json").write_text(
        json.dumps(traffic))
    (bench_dir / "limits" / "sim-fit.c12.json").write_text(json.dumps(
        {"loss_gap.call1": 0.1, "change_gap.call2": 0.1}))
    (bench_dir / "metrics" / "sim.rounds.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    # the manifest takes entries; no existing file under chipbench/ changes
    m = json.loads((tmp_path / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "squeezenet1_1.c12", "source": "x",
                         "file": "chipbench/configs/squeezenet1_1.c12.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "sim-fit.c12", "config":
                           "squeezenet1_1.c12", "traffic": "trainer-fit-5",
                           "chips": 1, "why": "x"})
    m["per_layer"].append({"name": "sim.rounds", "unit": "rounds",
                           "better": "higher", "source": "program_counter",
                           "layer": "x", "moves": "sim_round_ms",
                           "workloads": ["sim-fit.c12"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))

    found = bench.resolve(tmp_path, "sim-fit.c12")
    assert found["config"]["clinics"] == 12
    assert found["traffic"]["check_calls"] == 5
    assert found["limits"]["change_gap.call2"] == 0.1
    assert found["model"].param_shapes()["conv1"]["w"] == (3, 3, 3, 64)
    readers = bench.metric_readers(tmp_path, found["manifest"],
                                   found["cell"])
    assert set(readers) == {"sim.rounds"}
    assert readers["sim.rounds"].read(type("C", (), {"rounds": 7})) == 7.0
    old = bench.resolve(tmp_path, CELL)
    assert "sim.rounds" not in bench.metric_readers(
        tmp_path, old["manifest"], old["cell"])
    after = digests(bench_dir)
    assert {k: after[k] for k in before} == before
