"""Fixtures of the benchmark's own tests: a temporary copy of `bench/` in
which reduced configurations and mixes are added as files, beside a
`BENCHMARK.json` that names reduced cells. Run with
`JAX_PLATFORMS=cpu python -m pytest bench/tests` from the checkout root."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TINY_CELLS = [
    {"name": "granite-tiny.backlog", "config": "granite-tiny",
     "traffic": "tiny-backlog", "chips": 1, "why": "test"},
    {"name": "starcoder2-tiny.poisson", "config": "starcoder2-tiny",
     "traffic": "tiny-poisson", "chips": 1, "why": "test"},
]


def make_copy(dst: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark at `dst/bench`, with the reduced
    configurations and mixes added, a `BENCHMARK.json` naming the
    reduced cells beside it, and the program's `src` linked in."""
    bench = dst / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name in ("granite-tiny", "starcoder2-tiny"):
        shutil.copy(DATA / f"{name}.json", bench / "configs")
    for name in ("tiny-backlog", "tiny-poisson"):
        shutil.copy(DATA / f"{name}.json", bench / "traffic")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = TINY_CELLS
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    (dst / "src").symlink_to(ROOT / "src")
    return bench


@pytest.fixture
def tiny_bench(tmp_path) -> pathlib.Path:
    return make_copy(tmp_path)
