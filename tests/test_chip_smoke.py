"""chip_smoke.py on the CPU: its LM and frame phases at smoke sizes (the
device check belongs to the chip), and its refusal to report a result
anywhere but on a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro import configs

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lm_phase_serves_resumes_and_agrees(smoke):
    rep = smoke.lm_phase(configs.smoke_config("stablelm-3b"), seed=0,
                         n_slots=4, max_len=128, block_size=8,
                         max_new_tokens=4)
    assert rep["completed"] == 8
    assert rep["prefill_tokens_skipped"] > 0
    for key in ("logits_rel_err", "rows_rel_err", "live_rel_err"):
        assert 0.0 < rep[key] <= smoke.TICK_RTOL, key
    assert not rep["tick_has_kernel"]      # interpret mode off the chip


def test_frame_phase_completes_both_frontends(smoke):
    rep = smoke.frame_phase(configs.get("lenet5").smoke_config(), seed=0,
                            n_endpoints=4, duration_s=0.5, sc_frames=1,
                            sc_bits=(5,))
    assert rep["sc"] == rep["binary"] > 0


_FOUR_CHIP_PROG = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import configs
rep = smoke.four_chip_phase(configs.smoke_config("stablelm-3b"), seed=0,
                            max_len=64, block_size=8)
assert len(set(rep["sharded"]["devices"])) == 4, rep
print("FOUR_CHIP_OK", rep)
"""


def test_four_chip_phase_on_virtual_devices():
    """The --four-chips path on four virtual CPU devices (a child process:
    the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _FOUR_CHIP_PROG,
                        str(ROOT / "chip_smoke.py")], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR_CHIP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_no_result_off_tpu(tmp_path, where):
    """Off a TPU, and in a directory holding nothing of the repo but the
    script, it exits non-zero and never prints the ok line."""
    script, env = ROOT / "chip_smoke.py", dict(os.environ,
                                                JAX_PLATFORMS="cpu")
    cwd = ROOT
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
        env.pop("PYTHONPATH", None)
        cwd = tmp_path
    r = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
