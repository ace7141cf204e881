"""The shape of chip_smoke.py's result line, pinned (a previous bring-up was
lost on it), and its refusal to pass without a TPU.

The script has no switch for tests: sizes are shrunk and its device check
is stood in for from here.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO_ROOT))
import chip_smoke  # noqa: E402


def _tiny_conv(n_examples):
    from deeplearning4j_tpu.models import LeNet
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n_examples, 28, 28, 1), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, n_examples)]
    return LeNet().init(), (x, y)


def test_main_prints_exactly_the_result_line_last(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "LM", dict(
        vocab_size=64, seq_len=32, embed=32, n_layers=2, n_heads=2))
    monkeypatch.setattr(chip_smoke, "LM_BATCH", 4)
    monkeypatch.setattr(chip_smoke, "LM_COMPARE_BATCH", 2)
    monkeypatch.setattr(chip_smoke, "SERVE", dict(
        max_slots=3, max_seq=32, block_size=4))
    monkeypatch.setattr(chip_smoke, "SERVE_NEW_TOKENS", 4)
    monkeypatch.setattr(chip_smoke, "CONV", dict(
        batch=8, image=28, fit_steps=2, epoch_batches=2))
    monkeypatch.setattr(chip_smoke, "build_conv", _tiny_conv)
    monkeypatch.setattr(chip_smoke, "accelerators",
                        lambda: jax.devices()[:1])

    assert chip_smoke.main([]) == 0

    out = capsys.readouterr().out
    lines = [line for line in out.splitlines() if line.strip()]
    # every phase reported on an EARLIER line
    for phase in ("lm:", "serve:", "conv:"):
        assert any(line.startswith(phase) for line in lines[:-1])
    assert out.endswith(lines[-1] + "\n")          # nothing after it
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["ok"] is True
    device = jax.devices()[0]
    assert result["device"] == {"platform": device.platform,
                                "kind": device.device_kind,
                                "count": len(jax.devices())}


def test_without_a_tpu_the_script_fails_and_claims_nothing():
    """Unpatched, as the driver runs it, on a machine whose JAX is held to
    the CPU: a non-zero exit and no ok-true line."""
    r = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")],
        cwd=str(REPO_ROOT), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
