"""A configuration, a mix and a metric are added by adding files under
`bench/` and entries in `BENCHMARK.json`, with no other edit."""
import json
import os
import shutil

from bench import harness


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = tmp_path / "bench"
    shutil.copy(b / "configs" / "femnist_mlp.json",
                b / "configs" / "tiny_mlp.json")
    shutil.copy(b / "configs" / "femnist_mlp.py", b / "configs" / "tiny_mlp.py")
    mix = json.loads((b / "traffic" / "c10s10-g13-fedavg_sched.json")
                     .read_text())
    mix["scenarios"] = [{"clusters": 2, "sats": 5, "stations": 3}]
    (b / "traffic" / "c2s5-g3-fedavg_sched.json").write_text(json.dumps(mix))
    (b / "limits" / "tiny-cell.json").write_text(json.dumps(
        {"first_gap": 0.1}))
    (b / "metrics" / "tiny_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.setup_s\n")
    bench["configs"].append({"name": "tiny_mlp", "source": "x",
                             "file": "bench/configs/tiny_mlp.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny_mlp",
                               "traffic": "c2s5-g3-fedavg_sched",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "tiny_metric", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "setup_s",
                               "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.load_spec("tiny-cell", root=str(tmp_path))
    assert spec["mix"]["scenarios"][0]["clusters"] == 2
    assert spec["limits"] == {"first_gap": 0.1}
    assert spec["model"].forward_flops(spec["cfg"]) == 93_072
    names = [m["name"] for m in spec["metrics"]]
    assert "tiny_metric" in names and "rounds_per_s" in names
    read = harness.reader("tiny_metric", root=str(tmp_path))

    class Ctx:
        setup_s = 1.5
    assert read(Ctx) == 3.0
    # A metric listed for other cells only is left out of this one.
    other = harness.load_spec("mlp-fedavg_sched-c10s10-g13",
                              root=str(tmp_path))
    assert "tiny_metric" not in [m["name"] for m in other["metrics"]]


def test_every_listed_metric_has_a_reader():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"])), m["name"]
    for cell in bench["workloads"]:
        spec = harness.load_spec(cell["name"])
        assert spec["limits"], cell["name"]
