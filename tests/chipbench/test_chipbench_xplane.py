"""The trace reduction: busy time as the union of device op intervals,
idle gaps labelled by the host event that overlaps them most, per-op
device time; on hand-made planes and on a trace recorded on a TPU v5e."""

import gzip
import os
import shutil
import sys
from types import SimpleNamespace as NS

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))

import pytest  # noqa: E402

from chipbench import xplane  # noqa: E402

RECORDED = os.path.join(HERE, "data", "serve_decode.xplane.pb.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


def test_busy_is_the_union_of_overlapping_ops():
    dev = _plane("/device:TPU:0", {"XLA Ops": [
        ("a", 0, 100), ("b", 50, 100), ("c", 400, 100)]})
    host = _plane("/host:CPU", {"python3": [("tick", 120, 300),
                                            ("short", 150, 10)]})
    r = xplane.reduce_planes([dev, host], window_s=1e-6)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["devices"] == 1
    assert r["ops"] == pytest.approx({"a": 100e-9, "b": 100e-9,
                                      "c": 100e-9})
    assert r["idle_gaps"] == [["tick", pytest.approx(250e-9)]]
    assert r["device_ops"][0][1] == pytest.approx(100e-9)


def test_busy_is_averaged_over_devices_that_ran():
    d0 = _plane("/device:TPU:0", {"XLA Ops": [("a", 0, 100)]})
    d1 = _plane("/device:TPU:1", {"XLA Ops": [("a", 0, 300)]})
    idle = _plane("/device:TPU:2", {"XLA Ops": []})
    r = xplane.reduce_planes([d0, d1, idle], window_s=1e-6)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["ops"]["a"] == pytest.approx(400e-9)


def test_no_device_ops_gives_no_busy_time():
    r = xplane.reduce_planes([_plane("/host:CPU", {"python3": []})], 1.0)
    assert r["devices"] == 0 and r["busy_s"] == 0.0
    assert xplane.op_seconds(r, ("x",)) is None


def test_recorded_v5e_trace(tmp_path):
    """A 0.3 s trace of the butterfly chat cell's serving loop under load,
    recorded on one TPU v5e (gzipped): the reduction finds one busy
    device, the sandwich kernels by name, and no more busy time than the
    window or the ops' summed time."""
    path = tmp_path / "serve_decode.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = xplane.reduce_file(str(path), window_s=0.3)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.281892948, rel=1e-6)
    assert r["busy_s"] <= min(0.3, sum(r["ops"].values()))
    assert xplane.op_seconds(r, ("sandwich_matmul",)) == pytest.approx(
        0.006447765, rel=1e-6)
    assert len(r["device_ops"]) == 10
    assert all(g[1] > 0 for g in r["idle_gaps"])
