"""The benchmark finds every cell, configuration and metric by name, and
its names and units keep to the benchmark contract's alphabet."""
import json
import os
import re
import shutil

import pytest

from bench import check, harness

ROOT = os.path.dirname(harness.BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.benchmark()


def test_names_and_units(spec):
    names = [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [w[k] for w in spec["workloads"] for k in ("config", "traffic")]
    names += [m["name"] for k in ("end_to_end", "per_layer") for m in spec[k]]
    names += [r for c in spec["configs"] for r in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")


def test_every_entry_has_its_file(spec):
    for c in spec["configs"]:
        assert harness.config(c["name"])["name"] == c["name"]
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        work = harness.workload(w["name"])
        assert (work["config"], work["chips"]) == (w["config"], w["chips"])
        assert work["limits"] and set(work["limits"]) <= set(check.NUMBERS)
    for c in spec["configs"]:
        cfg = harness.config(c["name"])
        harness.program_object(cfg["program"]["model"])
        harness.program_config(cfg)
    for m in spec["per_layer"]:
        reader = harness.bench_module("metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES) == (
            m["layer"], m["unit"], m["better"], m["moves"])


def test_new_cell_and_metric_are_found_by_name(spec, tmp_path):
    """A cell and a metric dropped into a copy of the benchmark are picked
    up, and no file that was there changes."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    work = harness.workload("cifar10.avg-t4")
    work.update(name="cifar10.avg-e2", traffic="avg-e2")
    work["job"]["local_epochs"] = 2
    (bench / "workloads" / "cifar10.avg-e2.json").write_text(
        json.dumps(work))
    (bench / "metrics" / "rounds.train.py").write_text(
        'LAYER = "entry"\nUNIT = "rounds"\nBETTER = "higher"\n'
        'MOVES = "train_samples_per_s"\n\n\n'
        'def read(facts):\n    return float(facts["rounds"])\n')
    new_spec = json.loads((root / "BENCHMARK.json").read_text())
    new_spec["workloads"].append({"name": "cifar10.avg-e2",
                                  "config": "resnet18-cifar10",
                                  "traffic": "avg-e2", "chips": 1,
                                  "why": "two local epochs"})
    new_spec["per_layer"].append({"name": "rounds.train", "unit": "rounds",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "entry",
                                  "moves": "train_samples_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(new_spec))

    found = harness.workload("cifar10.avg-e2", str(bench))
    assert found["job"]["local_epochs"] == 2
    assert harness.config(found["config"], str(bench))["model"]["image_size"] \
        == 32
    loaded = harness.benchmark(str(bench))
    names = [m["name"] for m in
             harness.cell_metrics(loaded, "cifar10.avg-e2", "per_layer")]
    assert names == ["rounds.train"]
    assert [m["name"] for m in harness.cell_metrics(
        loaded, "cifar10.avg-t4", "per_layer")][-2:] == ["stage_wait_share",
                                                         "rounds.train"]
    assert "stage_wait_share" not in [m["name"] for m in harness.cell_metrics(
        loaded, "cifar10.seq-t4", "per_layer")]
    reader = harness.bench_module("metrics", "rounds.train", str(bench))
    assert reader.read({"rounds": 12}) == 12.0
    for path, data in before.items():
        assert path.read_bytes() == data, path


def test_new_model_and_traffic_are_found_by_name(tmp_path):
    """A configuration and a workload that name a reference, an operation
    count and a traffic maker of their own get those files, with no file
    that was there edited."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for kind, name in (("reference", "tiny"), ("flops", "tiny"),
                       ("traffic", "tiny_images")):
        (bench / kind / f"{name}.py").write_text(f"KIND = {kind!r}\n")
    cfg = harness.config("resnet18-cifar10")
    cfg.update(name="tiny", reference="tiny", flops="tiny")
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    work = harness.workload("cifar10.avg-t4")
    work.update(name="tiny.avg", config="tiny", maker="tiny_images")
    (bench / "workloads" / "tiny.avg.json").write_text(json.dumps(work))

    found = harness.workload("tiny.avg", str(bench))
    parts = harness.cell_parts(harness.config(found["config"], str(bench)),
                               found, str(bench))
    assert [p.KIND for p in parts] == ["traffic", "reference", "flops"]
    # the cells that were there still get their own
    cfg = harness.config("resnet18-cifar10", str(bench))
    traffic, reference, flops = harness.cell_parts(
        cfg, harness.workload("cifar10.avg-t4", str(bench)), str(bench))
    assert hasattr(traffic, "cell_data") and hasattr(reference, "FAULTS")
    assert flops.round_count(cfg["model"], work["job"]).flops > 0
    for path, data in before.items():
        assert path.read_bytes() == data, path
