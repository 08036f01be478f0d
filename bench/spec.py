"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); each per-layer metric is a
reader in ``bench/metrics/<metric>.py``; a configuration's
``model_type`` names its model file, ``bench/models/<model_type>.py``.
Adding a cell, a mix, a metric or a configuration adds files and entries
and edits nothing here.

A model file is the only place that knows its model family. It exports,
each taking the configuration dict ``cfg``:

- ``program_config(cfg, name)``: the program's ``ArchConfig``;
- ``init_weights(cfg, key, dtype)``: the canonical weight tree, drawn
  from ``key`` (under ``jax.jit``, on the device);
- ``to_program(w, cfg)``: that tree rearranged into the program's own;
- ``reference_weights(cfg, seed)``: the canonical tree drawn again from
  the seed, as float32;
- ``Reference(cfg, weights, fp8=False)``: an object whose
  ``.logits(tokens)`` is the plain float32 reference at ``highest``
  precision, or with ``fp8=True`` the fp8 control;
- ``work(cfg)``: the family's work model, with ``token_flops(keys)``,
  ``prefill_flops(prompt_len)``, ``decode_attention_work(keys,
  kv_bytes)`` and ``prefill_attention_work(prompt_len, act_bytes)``
  (``bench/work.py``).

The harness (``run.py``, ``calibrate.py``, ``readers.py``) reaches the
model only through these, and helpers that know no family through
``bench/models/common.py``. A new family adds one model file, a
configuration and entries, and edits nothing; a dense model binds the
flags of ``bench/models/dense.py`` (``qwen3.py``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

from bench.traffic import Mix, load_mix

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file, as run
    mix: Mix
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    @property
    def model(self):
        """The configuration's model file (``bench/models/<type>.py``)."""
        return importlib.import_module(
            f"bench.models.{self.config['model_type']}")


def load_benchmark(path: pathlib.Path = ROOT / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: dict | None = None,
              root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``bench`` (by default ``BENCHMARK.json``),
    with its configuration and mix read from under ``root``."""
    bench = bench if bench is not None else load_benchmark(
        root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = load_mix(w["traffic"], root / "bench" / "traffic")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: "
                       f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
