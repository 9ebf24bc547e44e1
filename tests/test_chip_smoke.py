"""``chip_smoke.py`` rehearsed on the CPU at reduced size: the same
phases the chip runs (serve, logits against the float32 reference,
kernels against their oracles), so the script cannot rot between chip
runs.  On the CPU the script itself must refuse to run."""
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_a_host_without_a_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main()
    assert e.value.code == 1
    assert "no TPU found" in capsys.readouterr().err


def test_smoke_serving_and_logit_phases_at_reduced_size(smoke):
    unit, report = smoke.serve_phase(smoke.ARCHS, reduced=True,
                                     pool_blocks=4096, max_slots=2,
                                     chunk_tokens=64)
    assert report.aggregate.finished == report.aggregate.submitted == 12
    probes = smoke.logits_phase(unit, smoke.ARCHS)
    # float32 engine vs float32 reference: far inside the bf16 bound
    assert smoke.compare_probes(probes) < 1e-2


def test_smoke_kernel_phase_interpreted(smoke):
    smoke.kernel_phase(interpret=True, small=True)
