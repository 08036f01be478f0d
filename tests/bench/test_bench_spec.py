"""Every cell resolves by name to its files, and a cell added as data
alone loads: later changes add files and entries, never edit these."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from bench import spec, traffic

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = spec.load_cell(cell)
    assert c.config["model_type"]
    for name in ("program_config", "init_weights", "to_program",
                 "reference_weights", "Reference", "work"):
        assert callable(getattr(c.model, name)), name
    assert c.mix.classes
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    ends = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in ends
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in ends
        for w in m.get("workloads", CELLS):
            assert w in CELLS
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_configs_hold_their_sources_numbers():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert key in cfg
        assert cfg["serving"]["max_len"] % cfg["serving"]["block_size"] == 0


def test_a_cell_added_as_data_alone_loads(tmp_path):
    """A new mix file and a new entry, with no code: the cell loads and
    its schedule is drawn by the one generator."""
    root = tmp_path
    shutil.copytree(spec.ROOT / "bench" / "configs",
                    root / "bench" / "configs")
    shutil.copytree(spec.ROOT / "bench" / "traffic",
                    root / "bench" / "traffic")
    (root / "bench" / "traffic" / "bursty-chat.json").write_text(json.dumps({
        "loop": "open",
        "arrival": {"rate_rps": 1.0, "burst_rate_rps": 4.0, "burst_s": 3,
                    "period_s": 12},
        "classes": [{"weight": 1.0,
                     "prompt": {"median": 512, "sigma": 0.8, "min": 32,
                                "max": 1536},
                     "output": {"median": 128, "sigma": 0.7, "min": 16,
                                "max": 512}}],
        "prefix": {"groups": 4, "share": 0.5,
                   "length": {"median": 256, "sigma": 0.2, "min": 128,
                              "max": 512}},
        "ramp_s": 10}))
    bench = json.loads(json.dumps(BENCH))
    config = bench["configs"][0]["name"]
    bench["workloads"].append({"name": f"{config}.bursty-chat",
                               "config": config, "traffic": "bursty-chat",
                               "chips": 1, "why": "bursts"})
    cell = spec.load_cell(f"{config}.bursty-chat", bench, root)
    assert cell.mix.burst_rate_rps == 4.0
    reqs = traffic.schedule(cell.mix, 5, 20.0, 1000)
    assert reqs and all(len(r.prompt) >= 32 for r in reqs)
    # metrics without a workloads list apply to the new cell too
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)


def test_unknown_cell_and_device_are_errors():
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
