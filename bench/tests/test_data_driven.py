"""A cell of an existing traffic mix over a new configuration needs a new
file and a new ``workloads`` entry, and no edit to any file."""
import json

from bench import harness

from helpers import run


def test_new_config_file_and_entry_run_without_edits(tmp_path):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((harness.BENCH / "configs" / "graph500-22.json")
                     .read_text())
    cfg.update(name="graph500-9", scale=9)
    path = tmp_path / "graph500-9.json"
    path.write_text(json.dumps(cfg))
    bench["configs"].append({"name": "graph500-9", "source": "x",
                             "file": str(path), "reduced": ["scale"],
                             "why": "x"})
    bench["workloads"].append({"name": "graph500-9.bfs",
                               "config": "graph500-9", "traffic": "bfs",
                               "chips": 1, "why": "x"})
    c = harness.cell("graph500-9.bfs", bench)
    assert c.config["scale"] == 9 and c.traffic["app"] == "bfs"
    # metrics without a workloads list reach a new cell; listed ones not
    assert {m["name"] for m in c.end_to_end} == {
        "teps", "peak_hbm_gb", "setup_s"}
    assert not c.per_layer
    result, _ = run(c)
    assert result["correct"] and result["metrics"]["teps"]["value"] > 0
