"""The harness's contract with the data that defines a cell: every
workload's files exist, a cell's own file (where it has one) holds
only a check sample, every configuration's arch module has the
functions the harness calls, no code names a workload, and a cell added
as data alone rehearses, fails its control and fails a fault."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from _drive import BENCH, ROOT, cells

import harness
import unit

CELL_KEYS = {"check", "about"}
CHECK_KEYS = {"sample_tokens", "sample_requests"}
ARCH_FUNCTIONS = ("dims", "program_config", "init_weights", "forward",
                  "weight_bytes", "decode_work", "prefill_work")
# directories of data, found by name, where a workload may be named
DATA_DIRS = ("configs", "traffic", "cells")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _workload(cell):
    return next(w for w in _bench()["workloads"] if w["name"] == cell)


@pytest.mark.parametrize("cell", cells())
def test_each_workload_has_its_files(cell):
    w = _workload(cell)
    conf = next(c for c in _bench()["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(ROOT, conf["file"])), cell
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       w["traffic"] + ".json")), cell
    path = os.path.join(BENCH, "cells", cell + ".json")
    own = {}
    if os.path.isfile(path):
        with open(path) as f:
            own = json.load(f)
    assert set(own) <= CELL_KEYS, f"{cell}: {sorted(set(own) - CELL_KEYS)}"
    assert set(own.get("check", {})) <= CHECK_KEYS, cell
    # the cell file adds its check sample and overrides nothing
    loaded = harness.load_cell(cell)["cell"]
    assert loaded == dict(w, **({"check": own["check"]}
                                if "check" in own else {}))


def _archs():
    archs = set()
    for conf in _bench()["configs"]:
        with open(os.path.join(ROOT, conf["file"])) as f:
            archs |= {llm["arch"] for llm in json.load(f)["llms"]}
    return sorted(archs)


@pytest.mark.parametrize("arch", _archs())
def test_each_arch_module_has_the_contract(arch):
    mod = unit.arch_module(arch)
    missing = [f for f in ARCH_FUNCTIONS if not callable(getattr(mod, f, None))]
    assert not missing, f"bench/arch/{arch}.py lacks {missing}"


def test_no_code_names_a_workload():
    names = cells()
    found = []
    for d, subdirs, files in os.walk(BENCH):
        rel = os.path.relpath(d, BENCH)
        if rel.split(os.sep)[0] in DATA_DIRS or "__pycache__" in rel:
            continue
        for fn in files:
            if fn.endswith(".pyc"):
                continue
            with open(os.path.join(d, fn), errors="replace") as f:
                text = f.read()
            found += [(os.path.join(rel, fn), n) for n in names if n in text]
    assert not found


def _phi3(source):
    from repro.config import ModelConfig
    return ModelConfig(
        name="phi-3-vision-4.2b", source=source, family="vlm", n_layers=32,
        d_model=3072, n_heads=32, n_kv_heads=32, d_ff=8192,
        vocab_size=32064, rope_theta=10000.0, rms_eps=1e-05,
        frontend_dim=3072, n_prefix_tokens=0)


def _mamba2(source):
    from repro.config import ModelConfig, SSMConfig
    return ModelConfig(
        name="mamba2-2.7b", source=source, family="ssm", n_layers=64,
        d_model=2560, n_heads=0, n_kv_heads=0, d_ff=0, vocab_size=50288,
        rms_eps=1e-05, tie_embeddings=True,
        ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_kernel=4,
                      chunk_size=256, n_groups=1))


PHI3 = "https://huggingface.co/microsoft/Phi-3-vision-128k-instruct"
MAMBA2 = "https://huggingface.co/state-spaces/mamba2-2.7b (arXiv:2405.21060)"


@pytest.mark.parametrize("config,llm,expected", [
    ("phi3v-mamba2", "phi-3-vision-4.2b", lambda: _phi3(PHI3)),
    ("phi3v-mamba2", "mamba2-2.7b", lambda: _mamba2(MAMBA2)),
    ("phi3v-solo", "phi-3-vision-4.2b", lambda: _phi3(PHI3)),
])
def test_program_model_config_is_the_published_model(config, llm, expected):
    """The serving system's configuration of each LLM, as the harness
    built it while the SSM sub-configuration came from a separate hook."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        entry = next(m for m in json.load(f)["llms"] if m["name"] == llm)
    assert unit.program_model_config(entry) == expected()


PROBE = "phi3v-solo.contract-probe"
PROBE_MIX = {
    "arrival": "backlog", "backlog": 16, "popularity_alpha": 1.0,
    "prompt": {"mean": 40, "sigma": 0.5, "min": 4, "max": 128},
    "output": {"mean": 24, "sigma": 0.5, "min": 4, "max": 64},
    "about": "A short backlog that the contract test adds as data alone."}
PROBE_SCRIPT = """
import json, sys
sys.path[:0] = [{tests!r}, {bench!r}, {src!r}]
import _drive, harness, run
cell = {cell!r}
assert harness.__file__.startswith({bench!r}), harness.__file__
rc = run.main(["--workload", cell, "--seed", "7", "--seconds",
               str(_drive.REHEARSAL_S), "--trace", "0", "--rehearse"])
checks = _drive.control(cell, 11)
fault = _drive.faulted(cell, "token_altered", 5)
print(json.dumps({{"rc": rc, "passed": harness.passed(checks),
                  "control_passed": _drive.control_passed(checks),
                  "fault_correct": fault["correct"],
                  "fault_requests": [c["requests"]
                                     for c in fault["checks"].values()]}}))
"""


def test_a_cell_added_as_data_alone_runs_and_can_fail(tmp_path):
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    shutil.copytree(BENCH, copy / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # the cell as data: its workload entry and a traffic file
    bench = _bench()
    bench["workloads"].append({
        "name": PROBE, "config": "phi3v-solo", "traffic": "contract-probe",
        "chips": 1, "why": "a short backlog added as data alone"})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    (copy / "bench" / "traffic" / "contract-probe.json").write_text(
        json.dumps(PROBE_MIX))
    added = {"traffic/contract-probe.json"}
    # no other file of the copy differs from the benchmark's own
    for d, _, files in os.walk(copy / "bench"):
        for fn in files:
            rel = os.path.relpath(os.path.join(d, fn), copy / "bench")
            if rel not in added:
                assert filecmp.cmp(os.path.join(d, fn),
                                   os.path.join(BENCH, rel), shallow=False)
    code = PROBE_SCRIPT.format(tests=str(copy / "bench" / "tests"),
                         bench=str(copy / "bench"),
                         src=os.path.join(ROOT, "src"), cell=PROBE)
    p = subprocess.run([sys.executable, "-c", code], cwd=copy,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    rehearsal, verdicts = json.loads(lines[-2]), json.loads(lines[-1])
    assert rehearsal["correct"] is True
    assert rehearsal["attempted"] > 0 and rehearsal["failed"] == 0
    assert set(rehearsal["metrics"]) == {"tpot_p50_ms", "tpot_p90_ms",
                                         "output_tok_s", "setup_s"}
    # the fault is caught by a comparison of served tokens, not for
    # want of any
    assert all(n > 0 for n in verdicts.pop("fault_requests"))
    assert verdicts == {"rc": 0, "passed": True, "control_passed": False,
                        "fault_correct": False}
