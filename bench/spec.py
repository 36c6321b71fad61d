"""Find a cell, its configuration and its traffic mix by name.

Each lives in a file of its own under the benchmark's directory:
`cells/<cell>.json`, `configs/<config>.json`, `traffic/<traffic>.json`.
Nothing lists them in code, so a new one is a new file.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str, root: str = BENCH) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind} entry named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


# What a cell file shares with its entry in BENCHMARK.json.
LISTED = ("config", "traffic", "chips", "why")


def listed(workload: str, root: str = BENCH):
    """The workload's entry in the BENCHMARK.json beside `root`, if any."""
    path = os.path.join(os.path.dirname(root), "BENCHMARK.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        entries = json.load(f)["workloads"]
    return next((w for w in entries if w["name"] == workload), None)


def resolve(workload: str, root: str = BENCH):
    """(cell, config, traffic) dicts for a workload name; a cell file that
    departs from its entry in BENCHMARK.json is refused."""
    cell = load("cells", workload, root)
    entry = listed(workload, root)
    if entry is not None:
        differ = [k for k in LISTED if entry[k] != cell[k]]
        if differ:
            raise SystemExit(f"bench: cells/{workload}.json and "
                             f"BENCHMARK.json differ on {differ}")
    return cell, load("configs", cell["config"], root), \
        load("traffic", cell["traffic"], root)
