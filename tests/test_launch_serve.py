"""In-process smoke tests for the serving CLI (`python -m
repro.launch.serve`): the full entrypoint — arg parsing, engine/router
construction, trace generation + open-loop replay, fault arming, the
unified telemetry JSON, and Chrome-trace export — driven by calling
`main()` with a patched argv, so CI catches CLI breakage without a
subprocess (and without re-importing jax)."""

import json
import sys

import pytest

ARCH = "smollm-135m-smoke"


def _run_cli(monkeypatch, *argv):
    import repro.launch.serve as serve_cli

    monkeypatch.setattr(sys, "argv", ["repro.launch.serve", *argv])
    serve_cli.main()


def test_cli_paged_trace_with_armed_faults(monkeypatch, tmp_path, capsys):
    """Small paged trace with the fault injector armed at a rate high
    enough to actually fire recovery paths; the unified telemetry JSON
    must land and parse (summary + registry snapshot)."""
    out = tmp_path / "metrics.json"
    _run_cli(monkeypatch,
             "--arch", ARCH, "--requests", "3", "--slots", "2",
             "--max-len", "48", "--max-new", "4", "--pool", "paged",
             "--fault-seed", "0", "--fault-rate", "0.05",
             "--metrics-json", str(out))
    text = capsys.readouterr().out
    assert "[serve]" in text and "ttft" in text
    doc = json.loads(out.read_text())
    assert doc["schema"] == "repro.serve/telemetry-1"
    snap = doc["summary"]
    assert snap["requests_finished"] == 3
    assert snap["pool"]["kind"] == "paged"
    assert snap["ttft_ms"]["p50"] <= snap["ttft_ms"]["p95"]
    metrics = doc["metrics"]
    assert metrics["schema"] == "repro.obs/v1"
    fam = metrics["metrics"]["serve_requests_finished_total"]
    assert fam["samples"][0]["value"] == 3
    # the armed injector registered its per-site families
    assert "serve_fault_calls_total" in metrics["metrics"]


def test_cli_trace_out_and_metrics_interval(monkeypatch, tmp_path, capsys):
    """--trace-out exports a validator-clean Chrome trace covering every
    request's lifecycle; --metrics-interval exercises the periodic
    flusher (the final write still wins)."""
    from repro.obs.validate import validate_chrome_trace

    trace = tmp_path / "trace.json"
    out = tmp_path / "metrics.json"
    _run_cli(monkeypatch,
             "--arch", ARCH, "--requests", "3", "--slots", "2",
             "--max-len", "48", "--max-new", "4", "--pool", "paged",
             "--prefill-chunk", "8", "--admission", "incremental",
             "--trace-out", str(trace),
             "--metrics-json", str(out), "--metrics-interval", "0.05")
    text = capsys.readouterr().out
    assert f"wrote {trace}" in text
    doc = json.loads(trace.read_text())
    events = validate_chrome_trace(doc)
    names = {e["name"] for e in events}
    assert {"queue", "admit", "tick", "finish", "compile"} <= names
    finishes = [e for e in events if e["name"] == "finish"]
    assert len(finishes) == 3
    # one request lane per request, plus the engine lane
    assert {e["tid"] for e in events} >= {0, 1, 2, 3}
    doc2 = json.loads(out.read_text())
    assert doc2["summary"]["requests_finished"] == 3


def test_cli_two_replicas_writes_router_snapshot(monkeypatch, tmp_path,
                                                 capsys):
    """--replicas 2 routes the same trace through the Router; the JSON
    summary is the tier snapshot (aggregate SLO percentiles +
    per-replica engine detail) and the trace carries one pid per
    replica."""
    from repro.obs.validate import validate_chrome_trace

    out = tmp_path / "router.json"
    trace = tmp_path / "router_trace.json"
    _run_cli(monkeypatch,
             "--arch", ARCH, "--requests", "4", "--slots", "2",
             "--max-len", "48", "--max-new", "4", "--replicas", "2",
             "--rate", "50", "--mix", "bimodal",
             "--trace-out", str(trace),
             "--metrics-json", str(out))
    text = capsys.readouterr().out
    assert "replicas=2" in text and "[serve] router:" in text
    doc = json.loads(out.read_text())
    snap = doc["summary"]
    assert snap["replicas"] == 2
    assert snap["requests_finished"] == 4
    assert len(snap["per_replica"]) == 2
    assert sum(p["dispatched"] for p in snap["per_replica"]) == 4
    assert {"p50", "p95"} <= set(snap["latency_ms"])
    # both replicas publish into the one registry, split by label
    fam = doc["metrics"]["metrics"]["serve_requests_finished_total"]
    assert {s["labels"]["replica"] for s in fam["samples"]} == {"0", "1"}
    assert sum(s["value"] for s in fam["samples"]) == 4
    events = validate_chrome_trace(json.loads(trace.read_text()))
    finishes = [e for e in events if e["name"] == "finish"]
    assert len(finishes) == 4
    # replicas trace under their own pid (dispatch split is timing-
    # dependent, so only the label space is pinned, not the split)
    assert {e["pid"] for e in finishes} <= {0, 1}
    assert {e["pid"] for e in events if e["name"] == "tick"} == {0, 1}


def test_cli_rejects_bad_geometry(monkeypatch, tmp_path):
    with pytest.raises(SystemExit, match="no valid prompt length"):
        _run_cli(monkeypatch, "--arch", ARCH, "--requests", "2",
                 "--max-len", "16", "--max-new", "14",
                 "--min-prompt", "8")
    with pytest.raises(SystemExit, match="--replicas"):
        _run_cli(monkeypatch, "--arch", ARCH, "--replicas", "0")


def test_build_replicas_places_replicas_unless_a_mesh_spans_devices():
    """Without a mesh, replica i's weights and page pool sit on device i
    (8 virtual CPU devices); an engine whose context has a mesh is built
    unplaced, on the caller's weights."""
    import jax

    from repro.configs import registry
    from repro.kernels.context import ExecutionContext
    from repro.launch.serve import build_replicas
    from repro.serve import ServeEngine, loader

    cfg = registry.get("smollm-135m-butterfly-smoke")
    params = loader.init_params(cfg, seed=0)

    def make(i, p, ctx):
        return ServeEngine(cfg, p, slots=2, max_len=32, pool="paged",
                           replica=i, context=ctx)

    devices = jax.devices()
    engines = build_replicas(3, cfg, params, make)
    assert [e.devices() for e in engines] == [{d} for d in devices[:3]]
    sharded, = build_replicas(1, cfg, params, make,
                              ExecutionContext(mesh_shape=(4,)))
    assert sharded.ctx.mesh_layout() == "data=4"
    assert sharded._params is params
