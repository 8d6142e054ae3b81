"""Cells, configurations, mixes and metrics are found by name: a new file
and a new entry are picked up with no edit; a device without peaks is an
error."""
import json
import shutil

import pytest

from benchmarks.chip import harness


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    monkeypatch.setattr(harness, "HERE", chip)
    return tmp_path, chip


def test_new_files_are_found_with_no_edit(bench_copy):
    root, chip = bench_copy
    (chip / "configs" / "new-model.json").write_text(json.dumps(
        {"num_layers": 2}))
    (chip / "traffic" / "long.json").write_text(json.dumps(
        {"path": "colocated_mllm", "seq_len": 8192}))
    (chip / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run.x * 2\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "new-model.long.1chip",
                               "config": "new-model", "traffic": "long",
                               "chips": 1, "why": "a later cell"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = harness.benchmark(root)
    cell = harness.workload("new-model.long.1chip", bench)
    assert harness.config(cell["config"]) == {"num_layers": 2}
    assert harness.traffic(cell["traffic"])["seq_len"] == 8192
    names = [m["name"] for m in harness.per_layer(bench, cell["name"])]
    assert "new_metric" in names and "distill_kl_roofline" not in names
    assert harness.metric_reader("new_metric").read(
        harness.Run(x=21)) == 42


def test_every_cell_resolves(bench_copy):
    bench = harness.benchmark(harness.ROOT)
    for cell in bench["workloads"]:
        tr = harness.traffic(cell["traffic"])
        assert harness.config(cell["config"])
        assert harness.path_module(tr["path"]).Job
        assert set(harness.limits(cell["name"])) <= {
            "first_loss_gap", "loss_gap", "grad_gap", "grad_diff",
            "update_gap"}
        for m in harness.per_layer(bench, cell["name"]):
            assert callable(harness.metric_reader(m["name"]).read)


def test_unknown_device_kind_raises():
    assert harness.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak("cpu")
    with pytest.raises(KeyError):
        harness.peak("source")
