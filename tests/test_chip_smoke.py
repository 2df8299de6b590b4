"""chip_smoke.py's phases at a CPU size: the workflow wiring and its checks
(the chip run itself is at published widths), and the refusal to run, or
print a result, without a TPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as CS                                        # noqa: E402
from repro.configs import reduced                              # noqa: E402


def test_one_chip_workflow_at_cpu_size(tmp_path):
    cfg, tcfg = CS.model_config()
    r = CS.run_one_chip(reduced(cfg), tcfg, tmp_path, steps=3, batch=2,
                        seq=32, n=2, prompt_len=16, gen_len=4)
    assert len(r["losses"]) == 3
    assert r["max_abs_logit_diff"] <= CS.LOGIT_RTOL * r["max_abs_logit"]
    assert set(r["step_s"]) == {"init", "train", "serve", "check"}
    assert list(tmp_path.glob("chip-smoke-*.json"))


def test_one_chip_checks_fail_loudly(tmp_path, monkeypatch):
    """A decode path that disagrees with the forward fails the run."""
    monkeypatch.setattr(CS, "LOGIT_RTOL", -1.0)
    cfg, tcfg = CS.model_config()
    with pytest.raises(CS.SmokeFailure, match="decode logits differ"):
        CS.run_one_chip(reduced(cfg), tcfg, tmp_path, steps=2, batch=2,
                        seq=32, n=2, prompt_len=8, gen_len=2)


def test_four_chip_phase_wiring_on_one_device(tmp_path):
    cfg, tcfg = CS.model_config(CS.FOUR_ARCH)
    r = CS.run_four_chips(reduced(cfg), tcfg, tmp_path, mesh=(1, 1),
                          cut_layers=1, batch=2, seq=16, steps=2)
    assert set(r["losses"]) == {"full-mesh", "cut-mesh", "cut-device0"}
    assert r["max_rel_loss_diff"] == 0.0     # same device, same program


def test_script_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs 1 TPU chip" in r.stderr
    assert '"ok"' not in r.stdout
