"""Each chip cell's limits file against the verdict a benchmark run uses:
a run that reads every number at its lower reading (the worst sound run
on the chip) is correct, and one that reads any single number at its
upper reading (the least failing control or fault on the chip) is not."""

import json
import os
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import pytest  # noqa: E402

from chipbench import limits, manifest  # noqa: E402
from chipbench import serve_driver, train_driver  # noqa: E402


def _files():
    out = []
    for w in manifest.load_manifest()["workloads"]:
        with open(os.path.join(REPO, "chipbench", "limits",
                               w["name"] + ".json")) as f:
            doc = json.load(f)
        out.extend((w["name"], k) for k in doc)
    return out


def _verdict(workload, readings):
    cell = manifest.load_cell(workload)
    lim = limits.load(workload)
    if cell.traffic["driver"] == "train":
        return train_driver.verdict(readings, 0, lim)[0]
    readings = dict(readings, compared_tokens=cell.traffic["reference"]
                    ["min_tokens"])
    return serve_driver.verdict(readings, 0, 0, lim,
                                cell.traffic["reference"]["min_tokens"])[0]


def _readings(workload, which):
    with open(os.path.join(REPO, "chipbench", "limits",
                           workload + ".json")) as f:
        return {k: v[which] for k, v in json.load(f).items()}


@pytest.mark.parametrize("workload,number", _files())
def test_limit_separates_its_readings(workload, number):
    lower = _readings(workload, "lower")
    assert _verdict(workload, lower)
    upper = dict(lower, **{number: _readings(workload, "upper")[number]})
    assert not _verdict(workload, upper)
    lim = limits.load(workload)[number]
    assert lower[number] < lim < upper[number]
