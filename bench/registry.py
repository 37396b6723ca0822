"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

  * a configuration: ``configs/<name>.json`` (published keys, ``source``,
    ``reduced``, ``assumed``, ``reference``);
  * its plain reference: ``reference/<reference>.py``;
  * a traffic mix: ``traffic/<name>.json``, read by the generator its
    ``kind`` names (``traffic/<kind>.py``);
  * a cell: ``workloads/<name>.json`` (entry, optimizer, traced steps, limits);
  * the path the window drives: ``entries/<entry>.py``;
  * a per-layer metric: ``metrics/<name>.py``, whose ``read(run)`` returns
    a number or None.

Adding any of these is adding a file; no file here lists them.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _module(path: Path):
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, root: Path = ROOT, bench: Path | None = None):
        self.root = Path(root)
        self.dir = Path(bench) if bench else self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def cell(self, name: str) -> dict:
        """The cell's ``BENCHMARK.json`` entry merged with its file."""
        entry = next((w for w in self.spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        return {**self._json("workloads", name), **entry}

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def generator(self, traffic: dict):
        return _module(self.dir / "traffic" / f"{traffic['kind']}.py")

    def reference(self, config: dict):
        return _module(self.dir / "reference" / f"{config['reference']}.py")

    def entry(self, name: str):
        return _module(self.dir / "entries" / f"{name}.py")

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        return _module(self.dir / "metrics" / f"{metric}.py").read
