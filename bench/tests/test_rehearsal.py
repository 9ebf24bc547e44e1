"""Each cell rehearsed on the CPU at its configuration's reduced sizes,
through the same harness a measurement run takes; and the measurement
mode's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def _run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_measurement_mode_refuses_a_host_without_an_accelerator():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert "{" not in p.stdout


def test_a_checkout_of_the_benchmark_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse"], cwd=tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_each_cell(cell, trace, capsys):
    import run
    assert run.main(["--workload", cell, "--seed", str(2 ** 31 + 17),
                     "--seconds", "3", "--trace", str(trace),
                     "--rehearse"]) == 0
    out = capsys.readouterr()
    assert "compiles in window 0, traces 0" in out.out
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in bench[kind]
              if cell in m.get("workloads", [cell])}
    got = set(result["metrics"])
    # a CPU rehearsal reports no device metric
    device = {m["name"] for m in bench[kind] if m["source"] == "device_trace"}
    assert got == wanted - device
    assert "check gap." in out.err.strip().splitlines()[-2]
