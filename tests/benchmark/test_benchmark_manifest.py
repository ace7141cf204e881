"""``BENCHMARK.json`` against the contract's limits on names, units and
files, and ``run.py``'s behaviour where there is no chip.  Nothing here
touches the TPU library."""
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, REPO)

from benchmark import common, run  # noqa: E402

MANIFEST = common.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def all_names():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[section]:
            yield entry["name"]
    for w in MANIFEST["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in MANIFEST["configs"]:
        yield from c["reduced"]


def named_files():
    """Every file that the manifest, a cell or a configuration names."""
    for c in MANIFEST["configs"]:
        yield c["file"]
        family = common.load_json("configs", c["name"] + ".json")["family"]
        yield f"benchmark/flops/{family}.py"
        yield f"benchmark/reference/{family}.py"
    for w in MANIFEST["workloads"]:
        yield f"benchmark/workloads/{w['name']}.json"
        kind = common.load_json("workloads", w["name"] + ".json")["kind"]
        yield f"benchmark/traffic/{kind}.py"
    for m in MANIFEST["per_layer"]:
        yield f"benchmark/metrics/{m['name']}.py"
    yield "benchmark/peaks.json"


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_name_is_of_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    end_to_end = metric in MANIFEST["end_to_end"]
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(metric.get("workloads", ())) <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert metric["workloads"], "every per-layer metric lists its cells"
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_every_cell_reports_setup_and_each_kind_of_metric():
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MANIFEST["end_to_end"])
    for w in MANIFEST["workloads"]:
        assert len(run.cell_metrics(MANIFEST, w["name"], "end_to_end")) >= 2
        assert run.cell_metrics(MANIFEST, w["name"], "per_layer")
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_names_are_unique_and_every_configuration_is_used():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[section]]
        assert len(names) == len(set(names))
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in MANIFEST["workloads"]} == \
        {c["name"] for c in MANIFEST["configs"]}


@pytest.mark.parametrize("path", sorted(set(named_files())))
def test_named_file_exists_under_the_benchmarks_paths(path):
    assert os.path.isfile(os.path.join(REPO, path)), path
    assert any(path.startswith(p + "/") for p in MANIFEST["paths"])
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", path)


def test_the_command_names_only_files_under_paths():
    assert len(MANIFEST["command"]) <= 32
    for word in MANIFEST["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_no_width_is_reduced():
    width = re.compile(r"(_dim|_rank|hidden|intermediate|n_embd|n_inner|"
                       r"widths|expansion|head)")
    for c in MANIFEST["configs"]:
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if width.search(k)]


@pytest.mark.parametrize("section", ["configs", "workloads", "per_layer",
                                     "end_to_end"])
def test_run_py_holds_no_name_of_the_manifest(section):
    with open(os.path.join(REPO, "benchmark", "run.py")) as f:
        text = f.read()
    for entry in MANIFEST[section]:
        if entry["name"] == "setup_s":
            continue        # the contract's own word, in the usage text
        assert entry["name"] not in text, entry["name"]


def test_cell_and_manifest_agree():
    for w in MANIFEST["workloads"]:
        cell = common.load_json("workloads", w["name"] + ".json")
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert cell["limits"], "a cell with no limit is never correct"


def test_an_unknown_chip_has_no_peak():
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert common.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks_for("cpu")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 - 1, 2 ** 31 + 11,
                                  3_000_000_019])
def test_any_whole_number_seeds_a_key(seed):
    import jax
    a = jax.random.key_data(common.seed_key(seed))
    b = jax.random.key_data(common.seed_key(seed + 1))
    assert (a != b).any()
    assert (a == jax.random.key_data(common.seed_key(seed))).all()


def test_without_a_tpu_there_is_no_result_line():
    cell = MANIFEST["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        MANIFEST["command"] + ["--workload", cell, "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_without_the_program_there_is_no_result_line(monkeypatch, capsys):
    monkeypatch.setattr(run, "accelerators", lambda chips: ["a chip"])
    monkeypatch.setitem(sys.modules, "deeplearning4j_tpu", None)
    cell = MANIFEST["workloads"][0]["name"]
    code = run.main(["--workload", cell, "--seed", "1", "--seconds", "1"])
    assert code != 0
    assert capsys.readouterr().out.strip() == ""


def test_an_unknown_cell_is_refused(capsys):
    assert run.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out.strip() == ""
