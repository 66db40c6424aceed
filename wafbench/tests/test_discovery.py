"""A later PR adds a configuration, a mix, a generator and a per-layer
metric as new files and new entries of BENCHMARK.json; no file that is
there has to change."""

import json
import shutil
from pathlib import Path

from wafbench import harness

BENCH = Path(harness.__file__).resolve().parent


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path
    shutil.copytree(BENCH / "generators", root / "wafbench" / "generators")
    shutil.copytree(BENCH / "layer_metrics", root / "wafbench" / "layer_metrics")
    shutil.copytree(BENCH / "traffic", root / "wafbench" / "traffic")
    shutil.copytree(BENCH / "configs" / "operator-sample",
                    root / "wafbench" / "configs" / "operator-sample")
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())

    # a new configuration: a directory
    new_cfg = root / "wafbench" / "configs" / "two-rules-b"
    shutil.copytree(BENCH / "configs" / "operator-sample", new_cfg)
    bench["configs"].append({"name": "two-rules-b", "source": "test", "reduced": [],
                             "file": "wafbench/configs/two-rules-b/config.json", "why": "test"})
    # a new generator and a new mix that names it: two files
    (root / "wafbench" / "generators" / "echo_plan.py").write_text(
        "class Traffic:\n"
        "    def __init__(self, config_dir, mix, seed):\n"
        "        self.args = (config_dir.name, mix['plan'], seed)\n"
    )
    (root / "wafbench" / "traffic" / "echo-c1.json").write_text(
        json.dumps({"generator": "echo_plan", "plan": "p", "zero_growth": []}))
    # a new per-layer metric: one file
    (root / "wafbench" / "layer_metrics" / "reply_bytes_per_req.py").write_text(
        "SOURCE = 'program_counter'\n\ndef read(ctx):\n    return ctx['after']['x'] / 2\n")
    bench["workloads"].append({"name": "two-rules-b.echo-c1", "config": "two-rules-b",
                               "traffic": "echo-c1", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "reply_bytes_per_req", "unit": "B", "better": "lower",
                               "source": "program_counter", "layer": "ingest",
                               "moves": "verdicts_per_s", "workloads": ["two-rules-b.echo-c1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.Cell("two-rules-b.echo-c1", root=root)
    assert cell.config["name"] == "operator-sample"  # the copied file, read from the new directory
    assert cell.config_dir == new_cfg
    assert cell.traffic(7).args == ("two-rules-b", "p", 7)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "reply_bytes_per_req" in names
    assert "prefilter_false_positive_share" not in names  # lists other cells
    assert cell.reader("reply_bytes_per_req").read({"after": {"x": 10}}) == 5
    # and the cells that were there still resolve from the same tree
    old = harness.Cell("sample.salted-c2", root=root)
    assert old.mix["generator"] == "planned_bursts"
    assert len(old.traffic(1).connections) == 2


def test_every_metric_of_the_benchmark_has_its_reader():
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        for m in cell.metrics("per_layer"):
            assert callable(cell.reader(m["name"]).read), m["name"]
        assert (cell.config_dir / "corpus.jsonl").exists()


def test_same_seed_same_bytes_and_every_seed_the_same_bursts():
    cell = harness.Cell("crs-lite.ftw-salted-c1")
    a, b, c = cell.traffic(2**31 + 5), cell.traffic(2**31 + 5), cell.traffic(6)
    sa, sb, sc = a.stream(0), b.stream(0), c.stream(0)
    n = len(a.connections[0])
    first_a = [next(sa) for _ in range(n)]
    first_b = [next(sb) for _ in range(n)]
    first_c = [next(sc) for _ in range(n)]
    assert [a.salted(x, "c0") for x in first_a] == [b.salted(x, "c0") for x in first_b]
    # another seed: the same set of bursts, in another order, other salts
    key = lambda burst: (burst.lane, tuple(map(tuple, burst.parts)))
    assert sorted(map(key, first_a)) == sorted(map(key, first_c))
    assert list(map(key, first_a)) != list(map(key, first_c))
    assert a.salted(first_a[0], "c0") != a.salted(first_a[0], "c0")  # never the same bytes twice
