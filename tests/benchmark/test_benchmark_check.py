"""What decides ``correct``: the arithmetic of the comparison, its control
(the reference one precision below the configuration's, put in the
program's place) and the rest of a run driven with the timed path broken
underneath, at sizes a test can hold, on the CPU."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, run  # noqa: E402
from benchmark.check import train as check_train  # noqa: E402

BELOW = check_train.BELOW
CELLS = {"lm": ("tiny-gpt2", "tiny-gpt2.train-fit"),
         "conv": ("tiny-resnet", "tiny-resnet.train-ondevice")}


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


def drive(which, seed=7):
    """``run.py`` below its look for a chip, on the test-sized cell."""
    import jax
    cfg_name, cell_name = CELLS[which]
    manifest = common.load_manifest()
    stand_in = [w["name"] for w in manifest["workloads"]
                if w["traffic"] == data(cell_name)["traffic"]][0]
    return run.execute(stand_in, seed, 0.5, False, jax.devices()[:1],
                       manifest=manifest, cell=data(cell_name),
                       cfg=data(cfg_name))


# --------------------------------------------------------------- arithmetic
def sides():
    ref = {"losses": [10.0, 9.0],
           "grad_norms": {"a": 1.0, "b": 2.0, "c": 4.0, "bias": 1e-6},
           "delta_norms": {"a": 0.1, "b": 0.1, "c": 0.1, "bias": 1e-9}}
    return ref, json.loads(json.dumps(ref))


def test_equal_sides_read_nought():
    ref, program = sides()
    read = check_train.readings(program, ref)
    assert (read["loss_gap"], read["grad_gap"], read["delta_gap"]) == (0, 0, 0)
    assert read["_where"]["left_out"] == 1


def test_gaps_are_of_norms_against_the_leaf_or_the_median_leaf():
    ref, program = sides()
    program["losses"][1] = 9.9
    program["grad_norms"]["a"] = 1.3         # 0.3 over the median, 1.5
    program["grad_norms"]["c"] = 4.4         # 0.4 over its own 4
    program["delta_norms"]["b"] = 0.15
    read = check_train.readings(program, ref)
    assert read["loss_gap"] == pytest.approx(0.1)
    assert read["grad_gap"] == pytest.approx(0.3 / 1.5)
    assert read["delta_gap"] == pytest.approx(0.5)
    assert read["_where"]["grad_gap"] == "a"


def test_a_leaf_without_gradient_in_the_reference_is_left_out():
    ref, program = sides()
    program["grad_norms"]["bias"] = 3.0      # round-off, of the median's size
    program["delta_norms"]["bias"] = 0.1     # and a move of full size
    read = check_train.readings(program, ref)
    assert read["grad_gap"] == 0 and read["delta_gap"] == 0


def test_a_state_left_unchanged_reads_one():
    ref, program = sides()
    program["delta_norms"] = {k: 0.0 for k in program["delta_norms"]}
    assert check_train.readings(program, ref)["delta_gap"] == 1.0


def test_a_loss_that_is_no_number_reads_infinite():
    ref, program = sides()
    program["losses"][0] = float("nan")
    read = check_train.readings(program, ref)
    ok, compared = check_train.verdict(read, {"loss_gap": 0.1})
    assert not ok and compared["loss_gap"]["limit"] == 0.1


def test_verdict_holds_each_number_to_its_limit():
    read = {"loss_gap": 0.01, "grad_gap": 0.02, "_where": {}}
    limits = {"loss_gap": 0.1, "grad_gap": 0.1}
    assert check_train.verdict(read, limits)[0]
    assert not check_train.verdict(read, {**limits, "grad_gap": 0.01})[0]
    assert not check_train.verdict(read, {})[0], "no limit, never correct"
    ok, compared = check_train.verdict(read, limits,
                                       {"kernels_missing": (1, 0, False)})
    assert not ok and compared["kernels_missing"] == {"value": 1, "limit": 0}


# ------------------------------------------------- a sound run, then faults
@pytest.fixture(scope="module", params=sorted(CELLS))
def sound(request):
    return request.param, drive(request.param)


def test_a_sound_run_is_correct(sound):
    which, result = sound
    assert result["correct"], result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_step_ms", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    for number in result["compared"].values():
        assert set(number) == {"value", "limit"}


def test_the_control_one_precision_below_is_not_correct(sound):
    import jax
    which, _ = sound
    cfg_name, cell_name = CELLS[which]
    cfg, cell = data(cfg_name), data(cell_name)
    job = common.load_module("traffic", cell["kind"]).Job(
        cell, cfg, 7, jax.devices()[:1])
    batches = job.checked_batches()
    control = job.reference(batches, precision=BELOW[cfg["precision"]])
    read = check_train.readings(control, job.reference(batches))
    assert not check_train.verdict(read, cell["limits"])[0], read


def unchanged(fit):
    """The program's entry, with the state it leaves put back."""
    def broken(self, *args, **kwargs):
        before = (self.params, self.opt_state)
        import jax
        keep = jax.tree_util.tree_map(lambda a: a + 0, before)
        fit(self, *args, **kwargs)
        self.params, self.opt_state = keep
        return self
    return broken


def half_rows_stream(fit):
    """``fit`` on an iterator whose batches lose the second half of their
    rows: the mean is taken over the rest."""
    def broken(self, iterator, *args, **kwargs):
        return fit(self, ((x[:(len(x) + 1) // 2], y[:(len(y) + 1) // 2])
                          for x, y in iterator), *args, **kwargs)
    return broken


def half_rows_dataset(fit_on_device):
    """``fit_on_device`` with the second half of every minibatch left
    out."""
    def broken(self, x, y, *, batch_size, **kwargs):
        half = batch_size // 2

        def cut(a):
            a = a.reshape((-1, batch_size) + a.shape[1:])[:, :half]
            return a.reshape((-1,) + a.shape[2:])
        return fit_on_device(self, cut(x), cut(y), batch_size=half, **kwargs)
    return broken


FAULTS = {
    ("lm", "state unchanged"): ("MultiLayerNetwork", "fit", unchanged),
    ("lm", "half the batch"): ("MultiLayerNetwork", "fit", half_rows_stream),
    ("conv", "state unchanged"): ("ComputationGraph", "fit_on_device",
                                  unchanged),
    ("conv", "half the batch"): ("ComputationGraph", "fit_on_device",
                                 half_rows_dataset),
}


@pytest.mark.parametrize("which,fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(monkeypatch, which, fault):
    import deeplearning4j_tpu.nn.computation_graph as graph
    import deeplearning4j_tpu.nn.multilayer as multilayer
    owner, entry, breaker = FAULTS[which, fault]
    cls = getattr(multilayer if owner == "MultiLayerNetwork" else graph,
                  owner)
    monkeypatch.setattr(cls, entry, breaker(getattr(cls, entry)))
    result = drive(which)
    assert not result["correct"], result["compared"]


def test_a_loss_that_is_no_number_is_a_failed_step(monkeypatch):
    import deeplearning4j_tpu.nn.multilayer as multilayer
    real = multilayer.MultiLayerNetwork.get_score
    calls = {"n": 0}

    def get_score(self):
        calls["n"] += 1
        return float("nan") if calls["n"] > 3 else real(self)
    monkeypatch.setattr(multilayer.MultiLayerNetwork, "get_score", get_score)
    result = drive("lm")
    assert result["failed"] >= 1 and not result["correct"]
    assert result["compared"]["failed_steps"]["limit"] == 0
