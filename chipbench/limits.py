"""Per-cell limits of the numbers compared for ``correct``, one file per
cell: ``chipbench/limits/<workload>.json`` holding ``{name: {"limit":
number, "lower": reading, "upper": reading}}``. A cell without a file
compares against no limit and so cannot come out correct."""

from __future__ import annotations

import json
import os
from typing import Dict

from chipbench.manifest import ROOT


def load(workload: str, root: str = ROOT) -> Dict[str, float]:
    path = os.path.join(root, "chipbench", "limits", workload + ".json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}
