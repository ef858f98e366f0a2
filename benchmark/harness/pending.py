"""A cell of ``benchmark/pending_cells/``: files that are here, ran on the
chip and printed ``correct``, and entries that ``BENCHMARK.json`` does not
hold yet (the file's ``what`` and PERF.md section 7 say why).  Such a file
holds the cell's ``config`` and ``workload`` entries, the per-layer metrics
it brings (``per_layer``) and the names of the accepted metrics whose
``workloads`` it would join (``joins``).  The harness reads the
``BENCHMARK.json`` beside the ``benchmark/`` it was started from, so a
pending cell runs from a directory that holds the merged manifest and
links to the tree's code:

    python3 -m benchmark.harness.pending <cell> <dir>
    python3 <dir>/benchmark/run.py --workload <cell> --seed <n> --trace 1
"""
from __future__ import annotations

import copy
import json
import os
import sys

from . import manifest as manifest_mod


def entries(cell: str, root: str = manifest_mod.ROOT) -> dict:
    path = os.path.join(root, "benchmark", "pending_cells", f"{cell}.json")
    with open(path) as f:
        return json.load(f)


def merged(manifest: dict, cell: str, root: str = manifest_mod.ROOT) -> dict:
    """``manifest`` with the pending ``cell`` behind everything it has."""
    p = entries(cell, root)
    m = copy.deepcopy(manifest)
    m["configs"].append(p["config"])
    m["workloads"].append(p["workload"])
    for metric in m["end_to_end"] + m["per_layer"]:
        if metric["name"] in p["joins"]:
            metric["workloads"].append(cell)
    m["per_layer"].extend(p["per_layer"])
    manifest_mod.check_manifest(m)
    return m


def stage(cell: str, into: str, root: str = manifest_mod.ROOT) -> str:
    """Make ``into``: the merged manifest, and links to the code of
    ``root`` that a run or a probe of the cell starts from."""
    os.makedirs(into, exist_ok=True)
    for name in ("benchmark", "deepspeed_tpu", "scripts"):
        link = os.path.join(into, name)
        if not os.path.lexists(link):
            os.symlink(os.path.join(os.path.abspath(root), name), link)
    with open(os.path.join(into, "BENCHMARK.json"), "w") as f:
        json.dump(merged(manifest_mod.load_manifest(root), cell, root), f,
                  indent=1)
    return into


if __name__ == "__main__":
    stage(*sys.argv[1:3])
