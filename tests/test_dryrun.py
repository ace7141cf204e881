"""Hermeticity tests for the driver-graded multi-chip dry run.

The dry run is a CPU-mesh correctness check; it must pass even when the
default backend (the TPU in production) is poisoned.  Mirrors
the reference's always-runnable local-cluster proof
(dl4j-spark/src/test/java/org/deeplearning4j/spark/BaseSparkTest.java:46 —
``local[N]`` needs no real cluster).
"""
import pytest

from deeplearning4j_tpu.parallel import dryrun


def test_poisoned_default_backend_falls_back_to_subprocess(monkeypatch, capsys):
    """Any in-process failure (e.g. a TPU client that fails an init op)
    must route to the fresh JAX_PLATFORMS=cpu subprocess, not fail the run."""
    calls = []

    def poisoned(n_devices, devices):
        calls.append(n_devices)
        raise RuntimeError("simulated: libtpu client/terminal version mismatch")

    monkeypatch.setattr(dryrun, "_run_in_process", poisoned)
    dryrun.run(2)  # must not raise — subprocess completes the check
    # the stderr notice pins that the poison->fallback transition actually ran
    # (not e.g. a provision_devices shortcut straight to the subprocess).
    assert "falling back to hermetic" in capsys.readouterr().err
    assert calls == [2]


def test_child_never_respawns(monkeypatch):
    """The hermetic subprocess entry point must fail terminally, never
    re-exec (no fork bombs)."""
    spawned = []
    monkeypatch.setattr(dryrun, "_run_in_subprocess",
                        lambda n: spawned.append(n))

    def poisoned(n_devices, devices):
        raise RuntimeError("still broken in child")

    monkeypatch.setattr(dryrun, "_run_in_process", poisoned)
    with pytest.raises(RuntimeError, match="still broken in child"):
        dryrun._child_main(2)
    monkeypatch.setattr(dryrun, "provision_devices", lambda n: None)
    with pytest.raises(RuntimeError, match="could not provision"):
        dryrun._child_main(2)
    assert spawned == []


def test_dryrun_in_process_8_devices():
    """The full driver contract (dp*tp + pipeline/seq + expert steps) on the
    8-device CPU mesh, genuinely in process (no silent subprocess rescue)."""
    devices = dryrun.provision_devices(8)
    assert devices is not None
    dryrun._run_in_process(8, devices)
