"""Fixtures for the benchmark's own tests (CPU, tiny sizes).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


@pytest.fixture(scope="session", autouse=True)
def _no_repo_cache(tmp_path_factory):
    """Keep compiled programs of the tests out of the checkout."""
    import jax

    d = str(tmp_path_factory.mktemp("jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax.config.update("jax_compilation_cache_dir", d)


def checkout(tmp: Path, configs: dict, cells: list, extra=None) -> Path:
    """A checkout holding the benchmark's files plus the given test
    configurations (``{name: file in data/}``), cells and extra files
    (``{relative path: text}``); returns its root."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, src in configs.items():
        shutil.copy(DATA / src, tmp / "bench" / "configs" / f"{name}.json")
        man["configs"].append({"name": name, "source": "test",
                               "file": f"bench/configs/{name}.json",
                               "reduced": [], "why": "test"})
    shutil.copy(DATA / "tiny-backlog.json",
                tmp / "bench" / "traffic" / "tiny-backlog.json")
    man["workloads"] += cells
    names = [c["name"] for c in cells]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] += names
    for rel, text in (extra or {}).items():
        (tmp / rel).write_text(text)
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp


def load_run(root: Path):
    """The checkout's ``bench/run.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: The tiny test deployments: ``tiny-<name>`` is ``data/tiny-<name>.json``.
TINY = ("disgd", "dics", "disgd-lru", "dics-lfu", "dics-lfu-collide")


@pytest.fixture
def tiny(tmp_path):
    """``(run module, checkout root)`` with a cell ``tiny-<name>.ingest``
    on each tiny test deployment of :data:`TINY`."""
    root = checkout(tmp_path, {f"tiny-{a}": f"tiny-{a}.json" for a in TINY},
                    [{"name": f"tiny-{a}.ingest", "config": f"tiny-{a}",
                      "traffic": "tiny-backlog", "chips": 1, "why": "test"}
                     for a in TINY])
    return load_run(root), root
