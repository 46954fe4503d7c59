"""Each cell's size for the CPU tests, and the faults planted in it.

A cell ``<name>`` of ``BENCHMARK.json`` has a file
``perfbench/tests/cells/<name>.json``:
``{"why": ..., "config": {...}, "traffic": {...}, "faults": [...]}``. The
``config`` and ``traffic`` entries replace those keys of the cell's
configuration and traffic mix; each fault is found by name in
``perfbench/lib/faults.py``'s ``FAULTS``, in a ``FAULTS`` dict of the cell's
kind module, or in one of the module that the file's optional
``"fault_module"`` names (a dotted module path). A new cell's sizes and
faults are new files only.
"""
import importlib
import json
from pathlib import Path
from typing import Callable, Dict, List, Tuple

from perfbench.lib import faults, harness

CELLS_DIR = Path(__file__).resolve().with_name("cells")
NO_FILE = "<no cell file>"


def path(workload: str) -> Path:
    return CELLS_DIR / f"{workload}.json"


def load(workload: str) -> Dict:
    """The cell's test file; a cell without one is an error naming the
    file it needs."""
    p = path(workload)
    if not p.is_file():
        raise FileNotFoundError(
            f"cell {workload!r} has no CPU test file: add {p} holding "
            f'{{"config": {{...}}, "traffic": {{...}}, "faults": [...]}}')
    return json.loads(p.read_text())


def overrides(workload: str) -> Dict:
    cell = load(workload)
    return {"config": cell["config"], "traffic": cell["traffic"]}


def pairs(bench: Dict) -> List[Tuple[str, str]]:
    """Every (cell, fault) the cells' files list; a cell without a file
    gives one pair whose test then fails naming the missing file."""
    out = []
    for w in bench["workloads"]:
        names = load(w["name"])["faults"] if path(w["name"]).is_file() \
            else [NO_FILE]
        out += [(w["name"], name) for name in names]
    return out


def fault(bench: Dict, workload: str, name: str) -> Callable:
    """The context manager that plants fault ``name`` in the cell."""
    cell = load(workload)
    _, _, _, traffic = harness.find_cell(bench, workload)
    modules = [harness.load_kind(traffic["kind"])]
    if "fault_module" in cell:
        modules.append(importlib.import_module(cell["fault_module"]))
    return faults.find(name, modules)
