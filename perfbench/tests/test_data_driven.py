"""A later PR adds a cell by adding files: a configuration, a traffic mix
and a per-layer metric that did not exist are written to a directory of
their own, named in a BENCHMARK.json, and ``run.py`` finds them by name.
No file of the benchmark is edited. Also: the result line's shape, and
that a run without a chip refuses to measure."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "perfbench", "run.py")


def run_py(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *argv], env=env, text=True,
                          capture_output=True, timeout=600)


def test_new_cell_config_traffic_and_metric_without_an_edit(tmp_path):
    tiny = os.path.join(HERE, "tiny")
    with open(os.path.join(tiny, "configs", "tiny-serve.json")) as fh:
        config = json.load(fh)
    config["sliding_window"] = 32                      # a new configuration
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "configs" / "new-config.json").write_text(json.dumps(config))
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps({
        "generator": "open_loop", "schedule_seed": 3,
        "arrivals": {"process": "poisson", "rate": 5.0},
        "classes": [{"name": "c",
                     "prompt": {"dist": "uniform", "min": 10, "max": 30},
                     "output": {"dist": "uniform", "min": 2, "max": 6}}],
        "pre_window_s": 0.5, "drain_s": 30.0,
        "check": {"short": 1, "long": 1}}))
    (tmp_path / "layer_metrics" / "frames_run.py").write_text(
        "def read(ctx):\n    return ctx['counters']['frames']\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "perfbench/run.py"], "paths": ["."],
        "run_seconds": 2,
        "configs": [{"name": "new-config", "source": "test",
                     "file": "configs/new-config.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "new-cell", "config": "new-config",
                       "traffic": "new-mix", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.1, "source": "host_clock"},
                       {"name": "tokens_per_s", "unit": "tokens/s",
                        "better": "higher", "bound": 0.05,
                        "source": "host_clock"}],
        "per_layer": [{"name": "frames_run", "unit": "count",
                       "better": "lower", "source": "program_counter",
                       "layer": "frame program", "moves": "tokens_per_s"},
                      {"name": "window_compiles", "unit": "count",
                       "better": "lower", "source": "program_counter",
                       "layer": "frame program", "moves": "tokens_per_s"}]}))
    proc = run_py("--benchmark", str(tmp_path / "BENCHMARK.json"),
                  "--workload", "new-cell", "--seed", "5", "--seconds", "2",
                  "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}                     # no trace on a CPU
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"]["frames_run"]["value"] > 0
    assert line["metrics"]["frames_run"]["unit"] == "count"
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert line["device"]["platform"] == "cpu"
    # a rehearsal never prints a time or a rate
    proc = run_py("--benchmark", str(tmp_path / "BENCHMARK.json"),
                  "--workload", "new-cell", "--seed", "5", "--seconds", "2",
                  "--trace", "0", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1])["metrics"] == {}


def test_no_chip_no_result():
    proc = run_py("--workload", "chat-steady", "--seed", "0", "--seconds",
                  "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_unknown_workload_is_an_error():
    proc = run_py("--workload", "nope", "--seed", "0", "--seconds", "1",
                  "--trace", "0")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_benchmark_json_names_only_what_exists():
    """Every name in the real BENCHMARK.json leads to its file."""
    sys.path.insert(0, ROOT)
    from perfbench import harness
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"])
        cell.module("drivers", cell.config["kind"])
        cell.module("generators", cell.traffic["generator"])
        cell.module("configs", cell.config["reference"])
        assert {m["name"] for m in cell.metrics("end_to_end")} >= {"setup_s"}
        assert len(cell.metrics("end_to_end")) >= 2
        for m in cell.metrics("end_to_end"):
            assert hasattr(cell.module("e2e_metrics", m["name"]), "read")
        layer = [m for m in cell.metrics("per_layer")
                 if m["moves"] in {x["name"]
                                   for x in cell.metrics("end_to_end")}]
        assert layer, w["name"]
        for m in cell.metrics("per_layer"):
            assert m["moves"] in e2e
            assert hasattr(cell.module("layer_metrics", m["name"]), "read")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_benchmark_json_is_inside_the_contracts_limits():
    import re
    sys.path.insert(0, ROOT)
    from perfbench import harness
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    bench = harness.read_json(path)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 2 <= cells <= 24 and 1 <= len(bench["configs"]) <= 24
    assert all(line(w) for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(name.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == cells
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        assert line(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every file under paths is named from a name's characters and "/"
    tracked = subprocess.run(["git", "ls-files", "--cached", "--others",
                              "--exclude-standard", "perfbench"], cwd=ROOT,
                             text=True, capture_output=True).stdout.split()
    assert tracked and all(re.match(r"^[A-Za-z0-9_.\-/]+$", f)
                           for f in tracked)
