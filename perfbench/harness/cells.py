"""Finding the files of a cell by the names in ``BENCHMARK.json``. A later
PR adds a configuration, a traffic mix, a metric or a kernel by adding a
file; nothing here lists them."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # perfbench/
ROOT = os.path.dirname(HERE)
SIZE_KEYS = ("hidden", "ffn", "n_layers", "n_q_heads", "n_kv_heads",
             "head_dim", "vocab", "rope_theta", "norm_eps", "dtype")


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    key = f"perfbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: str | None = None) -> dict:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"perfbench: no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name, self.chips = name, int(self.entry["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        config_path = os.path.join(root, conf["file"])
        self.config = load_json(config_path)
        self.config["sizes"] = {k: self.config[k] for k in SIZE_KEYS}
        # <base>/configs/<config>.json  <->  <base>/traffic/<traffic>.json
        base = os.path.dirname(os.path.dirname(config_path))
        self.traffic_path = os.path.join(
            base, "traffic", self.entry["traffic"] + ".json")
        self.end_to_end = [m["name"] for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m["name"] for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "harness", "peaks.json"))
    if kind not in table:
        raise SystemExit(
            f"perfbench: device kind {kind!r} is not in the peaks table "
            f"({sorted(k for k in table if not k.startswith('_'))}); add it "
            f"with its source, it is never guessed")
    return table[kind]
