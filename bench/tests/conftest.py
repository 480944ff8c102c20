"""Fixtures of the benchmark's own tests (run them with
``python -m pytest bench/tests``).

``tiny_root`` is a copy of the benchmark in a temporary directory with
two extra cells, the repository's two configurations cut to 3,000 rows
under small traffic mixes, so that whole runs fit a CPU test."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ROWS = 3000
TINY_MIXES = {
    "tiny-closed": {"loop": "closed", "outstanding": 32, "max_qps": 1000,
                    "buckets": [16], "warmup_s": 0.5},
    "tiny-churn": {"loop": "open", "rate_qps": 40,
                   "insert_rows_per_min": 1200, "delete_rows_per_min": 1200,
                   "mutation_ticket_rows": 8, "buckets": [8, 16, 32, 64]},
}
TINY_CELLS = {"tiny.closed": ("deep-500k", "tiny-closed",
                              "deep-500k.closed"),
              "tiny.churn": ("deep-500k-stream", "tiny-churn",
                             "deep-500k-stream.churn")}


def make_tiny_root(dst: Path) -> Path:
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(REPO / "src", dst / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    for cell, (config, mix, like) in TINY_CELLS.items():
        cfg = json.loads((REPO / configs[config]["file"]).read_text())
        cfg["geometry"]["rows"] = TINY_ROWS
        name = f"tiny-{config}"
        (dst / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        spec["configs"].append(dict(configs[config], name=name,
                                    file=f"bench/configs/{name}.json"))
        (dst / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(TINY_MIXES[mix]))
        spec["workloads"].append({"name": cell, "config": name,
                                  "traffic": mix, "chips": 1,
                                  "why": "a CPU-sized copy of " + like})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


def quiet(*_):
    pass
