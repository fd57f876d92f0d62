"""``chip_smoke.py``'s phases, run at tiny sizes on the CPU.

On the CPU the decision kernels run in interpret mode, where the fused and
the stitched paths are bitwise-equal, so every phase must report a zero
deviation and (there being no chip) no compiled chip kernel. The four-chip
phases run in a child process with four virtual CPU devices. ``main()``
itself must refuse to run without a TPU.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


TINY_ENGINE = dict(n_clients=8, per_client=8, n_test=32, hw=8, conv1=4,
                   conv2=8, hidden=16, batch=4, local_steps=2, rounds=3,
                   eval_every=2, eval_size=32, m_cap=4)


def test_engine_phase(smoke):
    res = smoke.engine_phase(smoke.EngineSize(**TINY_ENGINE))
    assert res["max_dev"] == 0.0
    assert res["chip_kernel"] is False
    assert len(res["n_selected"]) == 2


def test_service_phase(smoke):
    mix = ((24, 3, "proposed"), (100, 2, "proposed"), (40, 2, "uniform"))
    res = smoke.service_phase(mix=mix, flushes=2)
    assert res["tenants"] == 7
    assert res["serve_compiles"] == 0
    assert res["max_dev"] == 0.0
    assert res["ties"] == 0
    assert res["chip_kernel"] is False


def test_schedule_phase(smoke):
    res = smoke.schedule_phase(n=300, rounds=2)
    assert res["max_dev"] == 0.0
    assert res["chip_kernel"] is False
    assert len(res["n_sel"]) == 2


def test_four_chip_phases():
    code = (
        "import json, sys; sys.path.insert(0, sys.argv[1]); "
        "import chip_smoke as s; "
        f"e = s.four_chip_engine_phase(s.EngineSize(**{TINY_ENGINE!r})); "
        "d = s.four_chip_schedule_phase(n=300, rounds=2); "
        "print(json.dumps([e, d]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    engine, sched = json.loads(out.stdout.strip().splitlines()[-1])
    for res in (engine, sched):
        assert res["devices"] == 4
        assert res["max_dev"] <= 4 * 1.1920929e-07


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
