"""The manifest, and the rule that everything is found by name."""

import copy
import json
import os

import pytest

from benchmark.manifest import Manifest
from contract import NAME, UNIT, problems

from bench_helpers import REPO, load_config


def test_committed_manifest_meets_the_contract():
    assert problems(Manifest()) == []


def test_every_name_and_unit_uses_only_the_allowed_characters():
    d = Manifest().data
    names = ([c["name"] for c in d["configs"]]
             + [k for c in d["configs"] for k in c["reduced"]]
             + [x for w in d["workloads"]
                for x in (w["name"], w["config"], w["traffic"])]
             + [m["name"] for m in d["end_to_end"] + d["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"])
               for m in d["end_to_end"] + d["per_layer"])
    assert all(m["better"] in ("lower", "higher")
               for m in d["end_to_end"] + d["per_layer"])


def test_every_data_file_the_manifest_names_exists():
    man = Manifest()
    for w in man.data["workloads"]:
        entry, config = man.config(w["config"])
        assert config["num_hidden_layers"] > 0
        assert "entry" in man.traffic(w["traffic"])
    for m in man.data["per_layer"]:
        spec = man.metric_spec(m["name"])
        assert {spec["layer"], spec["unit"], spec["moves"]} == {
            m["layer"], m["unit"], m["moves"]}
        assert callable(man.reader(spec["reader"]))


def test_config_files_keep_the_published_widths():
    m7 = load_config("mistral-7b-v0.3-d8")
    assert (m7["hidden_size"], m7["intermediate_size"],
            m7["num_attention_heads"], m7["num_key_value_heads"],
            m7["vocab_size"]) == (4096, 14336, 32, 8, 32768)
    c22 = load_config("codestral-22b-v0.1-d9")
    assert (c22["hidden_size"], c22["intermediate_size"],
            c22["num_attention_heads"], c22["num_key_value_heads"],
            c22["vocab_size"]) == (6144, 16384, 48, 8, 32768)
    for c in Manifest().data["configs"]:
        assert c["reduced"] == ["num_hidden_layers"]
        assert list(load_config(c["name"])["reduced"]) == c["reduced"]


def _broken(change):
    d = copy.deepcopy(Manifest().data)
    change(d)
    return d


BREAKS = {
    "space in a name": lambda d: d["workloads"][0].update(name="a b"),
    "greek unit": lambda d: d["per_layer"][0].update(unit="µs"),
    "unit with a space": lambda d: d["end_to_end"][0].update(
        unit="tokens per s"),
    "bound over a tenth": lambda d: d["end_to_end"][0].update(bound=0.2),
    "no setup_s": lambda d: d.update(end_to_end=[
        m for m in d["end_to_end"] if m["name"] != "setup_s"]),
    "extra key on a metric": lambda d: d["per_layer"][0].update(why="x"),
    "reduced names a width": lambda d: d["configs"][0].update(
        reduced=["hidden_size"]),
    "every cell on four chips": lambda d: [w.update(chips=4)
                                           for w in d["workloads"]],
    "moves an unreported metric": lambda d: d["per_layer"][0].update(
        moves="nothing_s"),
    "absolute command": lambda d: d.update(command=["/usr/bin/python3"]),
    "run_seconds too long": lambda d: d.update(run_seconds=52),
    "config file outside paths": lambda d: d["configs"][0].update(
        file="conf/x.json"),
    "unused config": lambda d: d["configs"].append(dict(
        d["configs"][0], name="spare", file="benchmark/configs/spare.json")),
    "unknown top-level key": lambda d: d.update(notes="x"),
}


@pytest.mark.parametrize("case", sorted(BREAKS))
def test_a_manifest_the_contract_refuses_is_reported(case, tmp_path):
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(_broken(BREAKS[case])))
    assert problems(Manifest(str(path))), case


def test_new_cell_config_traffic_metric_and_reader_are_found_by_name(
        tmp_path):
    """A later PR adds files and entries and edits nothing: everything
    new is looked up beside the new manifest first, everything old in
    this checkout."""
    root = tmp_path
    for sub in ("configs", "traffic", "metrics", "readers"):
        (root / "benchmark" / sub).mkdir(parents=True)
    (root / "benchmark" / "configs" / "third.json").write_text(
        json.dumps({"hidden_size": 64, "num_hidden_layers": 2}))
    (root / "benchmark" / "traffic" / "bursty.json").write_text(
        json.dumps({"entry": "cli.main", "codec": "raw"}))
    (root / "benchmark" / "metrics" / "new.count.json").write_text(
        json.dumps({"layer": "wire", "unit": "count", "moves": "ttft_s",
                    "source": "program_counter", "reader": "forty_two"}))
    (root / "benchmark" / "readers" / "forty_two.py").write_text(
        "def read(ctx, **args):\n    return 42.0\n")
    d = copy.deepcopy(Manifest().data)
    d["configs"].append({"name": "third", "source": "tests", "reduced": [],
                         "file": "benchmark/configs/third.json",
                         "why": "a third configuration"})
    for mix in ("bursty", "cold-raw"):
        d["workloads"].append({
            "name": f"{mix}.third", "config": "third", "traffic": mix,
            "chips": 1, "why": "a new cell"})
    d["per_layer"].append({"name": "new.count", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "wire", "moves": "ttft_s",
                           "workloads": ["bursty.third"]})
    (root / "BENCHMARK.json").write_text(json.dumps(d))
    man = Manifest(str(root / "BENCHMARK.json"))
    assert problems(man) == []
    assert man.config("third")[1]["hidden_size"] == 64
    assert man.traffic("bursty")["entry"] == "cli.main"
    assert man.traffic("cold-int8")["codec"] == "int8"  # the old ones too
    spec = man.metric_spec("new.count")
    assert man.reader(spec["reader"])({}) == 42.0
    assert man.reader("log_sum") is not None
    assert [m["name"] for m in man.metrics_for("bursty.third", "per_layer")
            if m["name"] == "new.count"] == ["new.count"]
    assert "new.count" not in [
        m["name"] for m in man.metrics_for("cold-raw.mistral-7b",
                                           "per_layer")]
    # nothing committed changed
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "readers", "forty_two.py"))


def test_the_harness_names_no_cell_configuration_or_metric_in_code():
    """Data-driven, as a requirement: no ``if cell == ...``."""
    d = Manifest().data
    names = ([w["name"] for w in d["workloads"]]
             + [c["name"] for c in d["configs"]]
             + [w["traffic"] for w in d["workloads"]]
             + [m["name"] for m in d["per_layer"]])
    code = ""
    for sub in ("", "drivers", "readers"):
        folder = os.path.join(REPO, "benchmark", sub)
        for f in sorted(os.listdir(folder)):
            if f.endswith(".py"):
                with open(os.path.join(folder, f)) as fh:
                    code += fh.read()
    found = [n for n in names if f'"{n}"' in code or f"'{n}'" in code]
    assert not found, found
