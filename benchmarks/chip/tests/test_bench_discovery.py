"""Every cell of BENCHMARK.json is found by name in files that exist,
and a cell or a metric added as new files is found without editing a
file that is there."""
import json
import re
import shutil

import pytest

import bench_tiny
import run as harness

HERE = bench_tiny.HERE
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return bench_tiny.bench()


def test_contract_keys_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"] == ["python3", "benchmarks/chip/run.py"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            mover = [x for x in b["end_to_end"] if x["name"] == m["moves"]]
            assert w in mover[0].get("workloads", [w])


@pytest.mark.parametrize(
    "cell", [w["name"] for w in bench_tiny.full_bench()["workloads"]])
def test_every_cell_names_files_that_exist(cell):
    """The committed cells, and the serving cell whose files are here."""
    b = bench_tiny.full_bench()
    entry = [w for w in b["workloads"] if w["name"] == cell][0]
    cfg = [c for c in b["configs"] if c["name"] == entry["config"]][0]
    assert (bench_tiny.ROOT / cfg["file"]).is_file()
    config = json.loads((bench_tiny.ROOT / cfg["file"]).read_text())
    assert (HERE / config["reference"]).is_file()
    assert set(config["reduced"]) == set(cfg["reduced"])
    traffic = json.loads((HERE / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    assert (HERE / "drivers" / f"{traffic['driver']}.py").is_file()
    assert (HERE / "cells" / f"{cell}.json").is_file()
    c = harness.Cell(bench_tiny.ROOT, cell, 1, [], bench=b)
    for m in c.metric_names("per_layer"):
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        reader = harness.load_module(HERE / "metrics" / f"{m['name']}.py",
                                     f"probe_{m['name']}")
        assert callable(reader.read)
    assert c.metric_names("end_to_end")


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    b = bench_tiny.full_bench()
    data = tmp_path / "data"
    shutil.copytree(HERE / "traffic", data / "traffic")
    shutil.copytree(HERE / "cells", data / "cells")
    # a new traffic mix and cell for an existing configuration
    mix = json.loads((HERE / "traffic" / "serve-chat.json").read_text())
    mix["rate_per_s"] = 9.0
    (data / "traffic" / "serve-new.json").write_text(json.dumps(mix))
    shutil.copy(HERE / "cells" / "internlm2-1.8b.serve-chat.json",
                data / "cells" / "internlm2-1.8b.serve-new.json")
    b["workloads"].append({"name": "internlm2-1.8b.serve-new",
                           "config": "internlm2-1.8b",
                           "traffic": "serve-new", "chips": 1,
                           "why": "a test"})
    b["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "itl_p95_ms"})
    cell = harness.Cell(bench_tiny.ROOT, "internlm2-1.8b.serve-new", 1, [],
                        bench=b, data=data)
    assert cell.traffic["rate_per_s"] == 9.0
    assert "new_metric" in [m["name"] for m in cell.metric_names(
        "per_layer")]
    assert cell.config["name"] == "internlm2-1.8b"
