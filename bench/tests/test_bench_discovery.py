"""A workload file and a metric file dropped into the benchmark's tree
are found by name, with no other file edited."""
import json
import shutil

from bench import harness


def test_dropped_files_are_listed(tmp_path):
    base = tmp_path / "bench"
    for sub in ("workloads", "metrics", "configs"):
        shutil.copytree(harness.BENCH / sub, base / sub)
    before_w = harness.list_workloads(base)
    before_m = harness.list_metrics(base)
    (base / "workloads" / "qwen3-1.7b.plain_steps.json").write_text(
        json.dumps({"kind": "plain_steps", "nodes": 2}))
    (base / "metrics" / "gossip_collective_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    assert harness.list_workloads(base) == sorted(
        before_w + ["qwen3-1.7b.plain_steps"])
    assert harness.list_metrics(base) == sorted(
        before_m + ["gossip_collective_ms"])
    wl = harness.load_workload("qwen3-1.7b.plain_steps", base)
    assert wl["kind"] == "plain_steps"
    assert harness.load_reader("gossip_collective_ms", base)(None) == 1.5


def test_metrics_apply_by_their_workloads_key():
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in harness.metrics_for(spec, "x",
                                                   "end_to_end")] == ["a", "b"]
    assert [m["name"] for m in harness.metrics_for(spec, "y",
                                                   "end_to_end")] == ["a"]


def test_every_listed_metric_has_a_reader():
    for name in harness.list_metrics():
        assert callable(harness.load_reader(name))


def test_the_cpu_is_refused(capsys):
    rc = harness.main(["--workload", "qwen3-1.7b.label_rounds", "--seed",
                       "3", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
