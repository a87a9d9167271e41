"""The shapes of the cell a record came from, for the readers that need
them (a kernel's roofline share wants the operations and bytes its calls
require, which follow from the configuration and the job).

A reader is handed the runner's record and nothing else, so the cell is
found from what the record holds: of the cells its metric lists in
``BENCHMARK.json``, the one whose job has the record's tokens a window and
whose family counts the record's operations a token.  Where none fits (an
older benchmark, a test's own root) there is nothing to read.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def of(run: Dict, metric: str, root: str = None) -> Optional[Tuple[Dict, Dict, object]]:
    """``(configuration, job, family module)`` of the train cell ``run``
    came from, looked for among the cells ``metric`` lists."""
    root = root or ROOT
    if run.get("kind") != "train" or "flops_per_token" not in run:
        return None
    try:
        bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    except OSError:
        return None
    entry = next((m for m in bench["per_layer"] if m["name"] == metric), None)
    if entry is None:
        return None
    cells = {c["name"]: c for c in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for name in entry.get("workloads", list(cells)):
        cell = cells.get(name)
        if cell is None:
            continue
        try:
            cfg = harness.load_json(
                os.path.join(root, configs[cell["config"]]["file"]))
            job = harness.load_json(
                harness.find(root, "traffic", cell["traffic"], ".json"))
            fam = harness.load_module(root, "families", cfg["family"])
        except (OSError, KeyError, ImportError):
            continue
        if job.get("kind") != "train":
            continue
        tokens = job["steps_per_dispatch"] * job["rows"] * job["seq"]
        if (tokens == run.get("tokens_per_window")
                and fam.train_flops_per_token(cfg, job["seq"])
                == run["flops_per_token"]):
            return cfg, job, fam
    return None
