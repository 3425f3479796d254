"""The harness finds a configuration, a traffic mix and a per-layer metric
by the name BENCHMARK.json gives them, so a later cell is new files and a
new entry, never an edit; and BENCHMARK.json keeps to its contract."""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402
from bench.arch import arch_of, family_name, load_config, load_family, load_reference  # noqa: E402
from bench.run import cell_spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (root / sub).mkdir(parents=True)
    conf = load_config("mixtral-8x22b-1L")
    conf["name"] = "mixtral-8x22b-2L"
    conf["num_hidden_layers"] = 2
    (root / "configs" / "mixtral-8x22b-2L.json").write_text(json.dumps(conf))
    mix = traffic.load_mix("cold_burst")
    mix["rate_rps"] = 0.25
    (root / "traffic" / "slow_chat.json").write_text(json.dumps(mix))
    (root / "metrics" / "steps_seen.new.py").write_text(
        "def read(rec):\n    return float(len(rec.steps))\n")

    assert arch_of(load_config("mixtral-8x22b-2L", root)).layers == 2
    assert traffic.load_mix("slow_chat", root)["rate_rps"] == 0.25
    rec = harness.Record(arch=arch_of(conf), mix=mix, seconds=1.0, steps=[1, 2, 3])
    assert harness.load_reader("steps_seen.new", root)(rec) == 3.0

    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "mixtral2.slow_chat", "config": "mixtral-8x22b-2L",
                               "traffic": "slow_chat", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_seen.new", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "engine",
                               "moves": "itl_p95_ms", "workloads": ["mixtral2.slow_chat"]})
    cell, e2e, per_layer = cell_spec(bench, "mixtral2.slow_chat")
    assert cell["config"] == "mixtral-8x22b-2L"
    assert ("setup_s", "s") in e2e and ("cold_ttft_s", "s") not in e2e
    assert per_layer == [("steps_seen.new", "steps")]


def test_every_name_in_benchmark_json_has_its_file():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        conf = load_config(c["name"])
        assert conf["name"] == c["name"]
        # the family it names: its plain reference and its adapter
        family = family_name(conf)
        assert (ROOT / conf["reference"]).is_file()
        assert (ROOT / "bench" / "families" / f"{family}.py").is_file()
        assert callable(load_reference(conf).logits)
        assert load_family(conf).arch_of(conf).name == c["name"]
    for w in BENCH["workloads"]:
        traffic.load_mix(w["traffic"])
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for m in BENCH["end_to_end"]:
        assert m["name"] == "setup_s" or m["name"] in harness.E2E


def test_benchmark_json_keeps_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for name in cells:
        _, e2e, per_layer = cell_spec(BENCH, name)
        assert len(e2e) >= 2 and per_layer
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for c in BENCH["configs"]:
        conf = load_config(c["name"])
        assert c["reduced"] == conf["reduced"] and c["source"] == conf["source"]
