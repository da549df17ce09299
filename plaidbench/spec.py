"""Find a cell's pieces by name: BENCHMARK.json, then one file per piece.

* a configuration: the ``file`` its ``configs`` entry names: the corpus
  (``corpus.CorpusSpec``'s keys), ``backend``, ``matmul_precision``,
  ``search``, ``limits`` and the optional ``shards`` (1 when absent): the
  passages are split over ``shards`` chips in contiguous pid ranges of
  ``per = ceil(passages / shards)``, shard ``i`` owning
  ``[i * per, min((i + 1) * per, passages))`` as ``plaid-sharded`` splits
  them, and ``correct`` judges PLAID shard by shard
  (``reference.plaid_topk``); a cell of such a configuration asks for
  ``shards`` chips;
* a traffic mix: ``plaidbench/traffic/<traffic>.json``;
* a per-layer metric's reader: ``plaidbench/metrics/<name>.py``, whose
  ``read(ctx)`` returns a number, or None where it finds nothing to read;
* the device peaks: ``plaidbench/peaks.json``, keyed by ``device_kind``.

Paths are relative to the directory that holds ``BENCHMARK.json`` (the root
of a checkout), so a later PR adds a configuration, a mix or a metric by
adding files and entries, and edits nothing that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib


@dataclasses.dataclass
class Cell:
    root: pathlib.Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> list[dict]:
        """This cell's end-to-end metrics, in BENCHMARK.json's order."""
        return [m for m in self.bench["end_to_end"] if self._has(m)]

    def per_layer(self) -> list[dict]:
        """Per-layer metrics read in this cell: those that list it, and
        those without a list whose ``moves`` metric this cell reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def shards(config: dict) -> int:
    """The configuration's ``shards``: a whole number, 1 when absent."""
    n = config.get("shards", 1)
    if isinstance(n, bool) or not isinstance(n, int) or n < 1:
        raise ValueError(f"configuration {config.get('name')!r}: shards must be a whole number >= 1, not {n!r}")
    return n


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(benchmark: str | pathlib.Path, workload: str) -> Cell:
    path = pathlib.Path(benchmark).resolve()
    root = path.parent
    bench = _load_json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {path} (have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "plaidbench" / "traffic" / f"{w['traffic']}.json")
    return Cell(root, bench, w, config, traffic)


def load_reader(root: pathlib.Path, name: str):
    """The ``read`` function of ``plaidbench/metrics/<name>.py``."""
    path = root / "plaidbench" / "metrics" / f"{name}.py"
    mod_name = "plaidbench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(root: pathlib.Path, device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = _load_json(root / "plaidbench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in plaidbench/peaks.json "
            f"(have {sorted(table['devices'])})"
        )
    return table["devices"][device_kind]
