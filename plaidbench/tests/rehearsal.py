"""A tiny cell to rehearse the harness on the CPU: a root directory laid
out as a checkout's, holding a copy of ``plaidbench/`` with the rehearsal's
configuration and traffic files added, and a ``BENCHMARK.json`` made from
the repository's own, its cells swapped for the tiny ones."""
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


def make_root(dest: pathlib.Path) -> pathlib.Path:
    pb = dest / "plaidbench"
    shutil.copytree(PKG, pb, dirs_exist_ok=True, ignore=shutil.ignore_patterns(
        ".index_cache", ".traces", "__pycache__", "tests"))
    shutil.copy(DATA / f"{CONFIG}.json", pb / "configs" / f"{CONFIG}.json")
    for name, traffic in TINY.values():
        shutil.copy(DATA / f"{traffic}.json", pb / "traffic" / f"{traffic}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tiny_of = {}
    for w in bench["workloads"]:
        kind = json.loads((PKG / "traffic" / f"{w['traffic']}.json").read_text())["kind"]
        tiny_of[w["name"]] = TINY[kind][0]
    bench["configs"] = [{
        "name": CONFIG, "source": "https://arxiv.org/abs/2205.09707",
        "file": f"plaidbench/configs/{CONFIG}.json", "reduced": ["passages", "centroids"],
        "why": "CPU rehearsal",
    }]
    bench["workloads"] = [
        {"name": name, "config": CONFIG, "traffic": traffic, "chips": 1, "why": "rehearsal"}
        for name, traffic in TINY.values()
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({tiny_of[w] for w in m["workloads"]})
    path = dest / "BENCHMARK.json"
    path.write_text(json.dumps(bench, indent=2))
    return path
