"""Tiny cells to rehearse the harness on the CPU: a root directory laid
out as a checkout's, holding a copy of ``plaidbench/`` with the rehearsal's
configuration and traffic files added, and a ``BENCHMARK.json`` made from
the repository's own, its cells swapped for the tiny ones: one chip each
(``tiny.*``), and the same corpus split over four (``tiny4.*``, which run
on four host devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
from __future__ import annotations

import json
import pathlib
import shutil

DATA = pathlib.Path(__file__).resolve().parent / "data"
PKG = DATA.parent.parent
REPO = PKG.parent
#: The tiny cell that stands for each kind of traffic.
TINY = {"open_loop": ("tiny.poisson", "tiny-poisson"), "closed_loop": ("tiny.bulk", "tiny-bulk")}
CONFIG = "rehearsal.k10"
#: The same corpus split over four chips, each kind of traffic on all four.
SHARDED = "rehearsal4.k10"
TINY4 = {kind: (name.replace("tiny.", "tiny4."), traffic) for kind, (name, traffic) in TINY.items()}
#: Between the largest probed set of a quarter (102 passages over 64 queries
#: of seed 5) and the smallest of the whole corpus (198): binding for one
#: index over all passages, never for a shard.
SHARDED_CAP = 144


def sharded_config() -> dict:
    cfg = json.loads((DATA / f"{CONFIG}.json").read_text())
    cfg.update(
        name=SHARDED, corpus="rehearsal4", shards=4, backend="plaid-sharded",
        deployment="none: a CPU rehearsal on four host devices, one shard each",
        search=dict(cfg["search"], candidate_cap=SHARDED_CAP),
    )
    return cfg


def make_root(dest: pathlib.Path) -> pathlib.Path:
    pb = dest / "plaidbench"
    shutil.copytree(PKG, pb, dirs_exist_ok=True, ignore=shutil.ignore_patterns(
        ".index_cache", ".traces", "__pycache__", "tests"))
    shutil.copy(DATA / f"{CONFIG}.json", pb / "configs" / f"{CONFIG}.json")
    (pb / "configs" / f"{SHARDED}.json").write_text(json.dumps(sharded_config(), indent=2))
    for name, traffic in TINY.values():
        shutil.copy(DATA / f"{traffic}.json", pb / "traffic" / f"{traffic}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tiny_of = {}
    for w in bench["workloads"]:
        kind = json.loads((PKG / "traffic" / f"{w['traffic']}.json").read_text())["kind"]
        tiny_of[w["name"]] = TINY[kind][0]
    bench["configs"] = [{
        "name": name, "source": "https://arxiv.org/abs/2205.09707",
        "file": f"plaidbench/configs/{name}.json", "reduced": ["passages", "centroids"],
        "why": "CPU rehearsal",
    } for name in (CONFIG, SHARDED)]
    bench["workloads"] = [
        {"name": name, "config": config, "traffic": traffic, "chips": chips, "why": "rehearsal"}
        for config, chips, cells in ((CONFIG, 1, TINY), (SHARDED, 4, TINY4))
        for name, traffic in cells.values()
    ]
    twin = {name: name4 for (name, _), (name4, _) in zip(TINY.values(), TINY4.values())}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            tiny = {tiny_of[w] for w in m["workloads"]}
            m["workloads"] = sorted(tiny | {twin[w] for w in tiny})
    path = dest / "BENCHMARK.json"
    path.write_text(json.dumps(bench, indent=2))
    return path
