"""The program's own names in a profiler trace (``chipbench/spans.py``):
device programs by name, the engine's and trainer's phases on the device
clock, and the idle time inside their window split by phase; on hand-made
planes and on traces recorded on a TPU v5e. Also pins what
``chipbench/xplane.py`` gives on the first recorded trace, so that the
spans' reduction is known to leave it alone."""

import gzip
import json
import os
import shutil
import sys
from types import SimpleNamespace as NS

HERE = os.path.dirname(__file__)
sys.path.insert(0, os.path.join(HERE, "..", ".."))

import pytest  # noqa: E402

from chipbench import spans, xplane  # noqa: E402

DECODE = os.path.join(HERE, "data", "serve_decode.xplane.pb.gz")
SERVE = os.path.join(HERE, "data", "serve_phases.xplane.pb.gz")
TRAIN = os.path.join(HERE, "data", "train_phases.xplane.pb.gz")


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


def _serve_planes():
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("a", 0, 100), ("b", 150, 150), ("c", 500, 100),
                    ("d", 900, 100)],
        "XLA Modules": [("jit_serve_decode(11)", 150, 150),
                        ("jit_serve_decode(11)", 500, 100),
                        ("jit_serve_decode(11)", 900, 100),
                        ("jit_serve_chunk_prefill(12)", 0, 100)]})
    host = _plane("/host:CPU", {"python3": [
        ("serve.admit", 50, 70), ("serve.decode", 120, 40),
        ("serve.compile", 130, 10), ("serve.readback", 160, 320),
        ("np.asarray(jax.Array)", 200, 250), ("serve.emit", 700, 100)]})
    return [dev, host]


def test_idle_in_the_phases_window_split_by_innermost_phase():
    sp = spans.reduce_planes(_serve_planes())
    assert sp["devices"] == 1
    assert sp["window_s"] == pytest.approx(750e-9)     # 50 .. 800
    assert sp["busy_s"] == pytest.approx(300e-9)
    assert sp["idle_s"] == pytest.approx(450e-9)
    want = {"serve.admit": 20, "serve.decode": 20, "serve.compile": 10,
            "serve.readback": 180, "no phase": 120, "serve.emit": 100}
    assert sp["idle_by_phase"] == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()})
    assert sum(sp["idle_by_phase"].values()) == pytest.approx(sp["idle_s"])
    assert sp["phases"]["serve.readback"] == {
        "count": 1, "seconds": pytest.approx(320e-9)}
    assert "np.asarray(jax.Array)" not in sp["phases"]


def test_programs_by_name_and_the_serve_readings():
    sp = spans.reduce_planes(_serve_planes())
    assert sp["modules"]["jit_serve_decode"] == {
        "count": 3, "seconds": pytest.approx(350e-9),
        "median_ms": pytest.approx(100e-6)}
    got = spans.readings(sp)
    assert got == pytest.approx({
        "decode_step_ms.serve": 100e-6, "chunk_step_ms.serve": 100e-6,
        "idle_in_ticks.serve": 60.0,
        # admit, decode, compile, emit: 150 of 750 ns
        "idle_host_work.serve": 20.0})


def test_train_reading_and_devices_averaged():
    def dev(n, busy):
        return _plane(f"/device:TPU:{n}", {"XLA Ops": busy})
    host = _plane("/host:CPU", {"python3": [
        ("train.batch", 0, 10), ("train.dispatch", 10, 10),
        ("train.readback", 20, 980)]})
    sp = spans.reduce_planes([dev(0, [("s", 15, 975)]),
                              dev(1, [("s", 5, 985)]), host])
    assert sp["devices"] == 2
    assert sp["idle_s"] == pytest.approx(20e-9)        # (25 + 15) / 2
    assert sp["idle_by_phase"] == pytest.approx(
        {"train.batch": 7.5e-9, "train.dispatch": 2.5e-9,
         "train.readback": 10e-9})
    assert spans.readings(sp) == pytest.approx({"idle_in_steps.train": 2.0})


def test_a_trace_without_phases_or_names_gives_no_reading():
    """What the program gave before it named its programs and phases:
    nothing to read, and nothing raised."""
    dev = _plane("/device:TPU:0", {"XLA Ops": [("a", 0, 10)],
                                   "XLA Modules": [("jit_traced(1)", 0, 10)]})
    sp = spans.reduce_planes([dev, _plane("/host:CPU", {"python3": []})])
    assert sp["window_s"] == 0.0 and sp["idle_by_phase"] == {}
    assert spans.readings(sp) == {}
    assert spans.readings(spans.reduce_planes([])) == {}


def test_segments_name_nested_and_adjacent_phases():
    phases = [(0, 10, "a"), (10, 30, "b"), (12, 14, "c"), (40, 50, "d")]
    assert spans.segments(phases, 0, 50) == [
        (0, 10, "a"), (10, 12, "b"), (12, 14, "c"), (14, 30, "b"),
        (30, 40, "no phase"), (40, 50, "d")]


def test_recorded_v5e_trace_before_phases(capsys):
    """The first recorded trace (an earlier program, every step
    ``jit_traced``): the programs read by their old name, no reading."""
    assert spans.main([DECODE]) == 0
    sp = json.loads(capsys.readouterr().out)
    assert sp["modules"]["jit_traced"]["count"] == 5
    assert sp["modules"]["jit_traced"]["median_ms"] == pytest.approx(
        69.572624, rel=1e-6)
    assert sp["phases"] == {} and sp["readings"] == {}


def test_recorded_v5e_serving_phases():
    """1.05 s of the butterfly chat cell on one TPU v5e, this program:
    one chunked prefill and five decode steps by name, the tick's phases,
    and the four serving readings; the breakdown names every op and gap
    after the program."""
    planes = list(spans.read_planes(SERVE))
    sp = spans.reduce_planes(planes)
    assert sp["modules"]["jit_serve_decode"]["count"] == 5
    assert sp["modules"]["jit_serve_chunk_prefill"]["count"] == 1
    assert set(sp["phases"]) == {"serve.admit", "serve.chunk",
                                 "serve.first_token", "serve.decode",
                                 "serve.readback", "serve.emit"}
    assert sp["window_s"] == pytest.approx(1.067801689, rel=1e-6)
    assert sum(sp["idle_by_phase"].values()) == pytest.approx(sp["idle_s"])
    assert spans.readings(sp) == pytest.approx({
        "decode_step_ms.serve": 60.653191,
        "chunk_step_ms.serve": 739.109652,
        "idle_in_ticks.serve": 2.4333021, "idle_host_work.serve": 1.6205742},
        rel=1e-6)
    r = xplane.reduce_planes(planes, window_s=sp["window_s"])
    names = [n for n, _ in r["device_ops"] + r["idle_gaps"]]
    assert not any("traced" in n or "closed_call" in n for n in names)
    assert any("paged_decode_attention" in n for n, _ in r["device_ops"])
    assert xplane.op_seconds(r, ("sandwich_matmul_fwd",)) > 0
    assert all(n.startswith("serve.") for n, _ in r["idle_gaps"])


def test_recorded_v5e_train_step_phases():
    """One train step of the butterfly training cell on one TPU v5e: the
    step's program by name, its three phases and the training reading."""
    planes = list(spans.read_planes(TRAIN))
    sp = spans.reduce_planes(planes)
    assert sp["modules"]["jit_train_step"]["count"] == 1
    assert sp["modules"]["jit_train_step"]["median_ms"] == pytest.approx(
        5051.674428, rel=1e-6)
    assert [k for k in sp["phases"]] == ["train.batch", "train.dispatch",
                                         "train.readback"]
    assert spans.readings(sp) == pytest.approx(
        {"idle_in_steps.train": 0.11225718}, rel=1e-6)
    r = xplane.reduce_planes(planes, window_s=sp["window_s"])
    assert all(n.startswith("train.") for n, _ in r["idle_gaps"])
    assert xplane.op_seconds(r, ("sandwich_matmul",)) > 0


def test_xplane_reduction_of_the_recorded_trace_is_pinned(tmp_path):
    path = tmp_path / "serve_decode.xplane.pb"
    with gzip.open(DECODE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = xplane.reduce_file(str(path), window_s=0.3)
    assert (r["devices"], r["window_s"], len(r["ops"])) == (1, 0.3, 182)
    assert r["busy_s"] == pytest.approx(0.281892948, rel=1e-9)
    assert sum(r["ops"].values()) == pytest.approx(0.556298878, rel=1e-9)
    gaps = [["np.asarray(jax.Array)", 0.002356147],
            ["np.asarray(jax.Array)", 0.002300333],
            ["np.asarray(jax.Array)", 0.002209033],
            ["PjitFunction(convert_element_type)", 0.001907758],
            ["PjitFunction(convert_element_type)", 0.001901283],
            ["np.asarray(jax.Array)", 0.00188908],
            ["np.asarray(jax.Array)", 0.001750092],
            ["PjitFunction(convert_element_type)", 0.00166512],
            ["XlaLinearize", 0.001454348],
            ["no host event", 3.678e-06]]
    assert [g[0] for g in r["idle_gaps"]] == [g[0] for g in gaps]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [g[1] for g in gaps], rel=1e-9)
    ops = [["%while.5 while tuple", 0.274551605],
           ["%closed_call.13 custom-call bf16[64,3,3,64]", 0.136093485],
           ["%copy.86 copy bf16[1,8193,16,3,64]", 0.02626757],
           ["%copy.87 copy bf16[1,8193,16,3,64]", 0.026042392],
           ["%copy.79 copy bf16[1,8193,16,3,64]", 0.023401229],
           ["%copy.83 copy bf16[1,8193,16,3,64]", 0.023154707],
           ["%constant_dynamic-update-slice_fusion.5 fusion "
            "bf16[30,8193,16,3,64]", 0.008774429],
           ["%constant_dynamic-update-slice_fusion.4 fusion "
            "bf16[30,8193,16,3,64]", 0.008672014],
           ["%constant_dynamic-slice_fusion.18 fusion bf16[1,8193,16,3,64]",
            0.008369939],
           ["%constant_dynamic-slice_fusion.19 fusion bf16[1,8193,16,3,64]",
            0.008361722]]
    assert [o[0] for o in r["device_ops"]] == [o[0] for o in ops]
    assert [o[1] for o in r["device_ops"]] == pytest.approx(
        [o[1] for o in ops], rel=1e-9)
