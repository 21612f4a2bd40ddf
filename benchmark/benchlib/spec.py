"""Finding things by name: a cell in BENCHMARK.json, its configuration and
traffic files, the family, per-layer reader, loop-kind and generator
modules. There is no central table: a later PR adds files and entries."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(name: str):
    """benchmark/families/<name>.py -> its FAMILY object."""
    return _load_module("families", name).FAMILY


def load_layer_reader(name: str):
    """benchmark/layers/<metric>.py -> its read(run) function. A metric
    named `<reader>.<tag>` is read by layers/<reader>.py: one quantity
    split over cells whose end-to-end metrics differ."""
    return _load_module("layers", name.split(".", 1)[0]).read


def load_loop(name: str):
    """A traffic file's `loop` -> the loop class: `statements` and
    `ingest` live in benchlib/loops.py, any other kind is
    benchmark/loops/<kind>.py and its LOOP."""
    from .loops import LOOPS
    return LOOPS.get(name) or _load_module("loops", name).LOOP


def load_generator(config: dict):
    """A configuration's optional `generator` -> the Dataset class of
    benchmark/generators/<name>.py; absent means TSBS devops cpu-only,
    benchlib/data.py."""
    name = config.get("generator")
    if name is None:
        from .data import Dataset
        return Dataset
    return _load_module("generators", name).Dataset


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads`, with everything it names resolved."""

    def __init__(self, workload: str, root: str = ROOT):
        self.benchmark = load_json(root, "BENCHMARK.json")
        found = [w for w in self.benchmark["workloads"]
                 if w["name"] == workload]
        if not found:
            raise SystemExit(f"BENCHMARK.json has no workload {workload!r}")
        self.entry = found[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        config_entry = next(c for c in self.benchmark["configs"]
                            if c["name"] == self.entry["config"])
        self.config = load_json(root, config_entry["file"])
        self.mix = load_json(BENCH_DIR, "traffic",
                             self.entry["traffic"] + ".json")

    def metrics(self, section: str) -> list:
        """The metrics of `end_to_end` or `per_layer` this cell reports: a
        metric without a `workloads` key belongs to every cell."""
        return [m for m in self.benchmark[section]
                if self.name in m.get("workloads", [self.name])]
