"""``benchmark/metrics/mla_project_device_pct.train``: the share of busy
time under latent attention's scope ``mla_project`` alone (the projections
and whatever hands the kernels their operands; the kernels themselves lie
under ``attn_full``): its entry in the manifest found by name, the
arithmetic on made-up self times, nothing to read, a program without the
scope, the small JoyAI trace recorded on the chip, and what its log says of
the form the kernels' keys came in."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402

NAME = "mla_project_device_pct.train"
CELL = "joyai-llm-flash-5l.train-fit-8k"
TRACE = os.path.join(HERE, "data", "small-joyai.xplane.pb")


def reader(name=NAME):
    return common.load_module("metrics", name)


def empty_ctx():
    return {"trace": None, "stretch": {"steps": 0}, "cell": {}, "cfg": {},
            "chips": 1, "flops_module": None, "flops_per_step": None,
            "peaks": {}, "counters_before": None, "counters_after": None}


def test_the_metric_is_in_the_manifest_by_name():
    manifest = common.load_manifest()
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": "Step program",
                     "moves": "train_step_ms", "workloads": [CELL]}
    # the layer is one the manifest already names, letter for letter
    assert "Step program" in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] != NAME}
    assert CELL in {w["name"] for w in manifest["workloads"]}
    assert os.path.exists(os.path.join(REPO, "benchmark", "metrics",
                                       NAME + ".py"))


def test_nothing_to_read_returns_nothing():
    assert reader().read(empty_ctx()) is None


@pytest.mark.parametrize("kept,want", [
    ("all", 14.0),                     # 10 + 4, the module's layer too
    ("no mla_project", None),          # attn_full alone is some other's
    ("nothing", None)])
def test_the_share_is_the_projection_scope_alone(monkeypatch, kept, want):
    mla = reader().mla
    fwd, bwd = "jit(train_step)/jvp(forward)/", \
        "jit(train_step)/transpose(jvp(forward))/"
    selfs = {fwd + "TransformerBlock/mla_project/dot_general": 6.0,
             bwd + "TransformerBlock/mla_project/transpose": 4.0,
             fwd + "TransformerBlock/attn_full/flash_fwd": 20.0,
             bwd + "TransformerBlock/attn_full/flash_bwd_dq": 30.0,
             fwd + "mtp/TransformerBlock/mla_project/dot_general": 4.0,
             fwd + "mtp/NextTokenMerge/dot_general": 5.0,
             "jit(train_step)/optimizer/add": 31.0}
    if kept == "no mla_project":
        selfs = {k: v for k, v in selfs.items() if "mla_project" not in k}
    table = None if kept == "nothing" else (selfs, 100.0)
    monkeypatch.setattr(mla, "scope_self_times", lambda path: table)
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane="made-up")
    got = reader().read(ctx)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.skipif(not os.path.exists(TRACE),
                    reason="the small trace is recorded on the chip")
def test_the_share_of_the_small_recorded_trace():
    """Three steps of the test-sized JoyAI cell: the scope is there, and its
    share lies under the latent share that holds the kernels too."""
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane=TRACE)
    project = reader().read(ctx)
    latent = reader("mla_device_pct.train").read(ctx)
    assert 0 < project < latent < 100
    by_scope, busy = reader().mla.under(ctx, ("mla_project", "attn_full"))
    assert project == pytest.approx(100 * by_scope["mla_project"] / busy)


def test_the_log_says_how_the_kernels_took_their_keys(monkeypatch, capsys):
    """Where the program counts ``flash_calls_traced_total`` the reader's
    log carries it; where it does not (the parent), nothing and no error."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.observability import registry
    from deeplearning4j_tpu.ops import flash_attention as F
    module = reader()
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "default_registry", lambda: fresh)
    assert module.key_forms() == {}
    q = jnp.ones((1, 1, 128, 192))
    F.flash_attention(q, kv=jnp.ones((1, 1, 128, 256)),
                      k_shared=jnp.ones((1, 1, 128, 64)), causal=True,
                      interpret=True)
    F.flash_attention(q, q, q, causal=True, interpret=True)
    assert module.key_forms() == {"parts": 1, "whole": 1}
    monkeypatch.setattr(module.mla, "scope_self_times", lambda path: (
        {"jit(train_step)/jvp(forward)/mla_project/dot_general": 1.0}, 4.0))
    ctx = dict(empty_ctx(), trace={"window_ns": 1.0}, xplane="made-up")
    assert module.read(ctx) == pytest.approx(25.0)
    assert "'parts': 1" in capsys.readouterr().err
