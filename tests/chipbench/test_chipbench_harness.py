"""The harness: cells found by name from data files, a new cell added by
new files alone, and no result without a TPU or without the program."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import pytest  # noqa: E402

from chipbench import manifest, peaks, run  # noqa: E402


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      _manifest()["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = manifest.load_cell(workload)
    assert cell.traffic["driver"] in ("serve", "train")
    assert callable(manifest.driver(cell.traffic["driver"]).run)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names
        mod = manifest.layer_metric(m["name"])
        assert mod.SOURCE == m["source"] and callable(mod.read)
    cfg = manifest.model_config(cell.config)
    assert cfg.d_model == cell.config["hidden_size"]


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A throwaway configuration, mix and metric, added as files and
    manifest entries in a copy, resolve without touching any file that
    was there."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "chipbench"), root / "chipbench")
    m = _manifest()
    cfg = json.load(open(os.path.join(
        REPO, "chipbench", "configs", "smollm-135m.json")))
    cfg["name"] = "throwaway"
    (root / "chipbench" / "configs" / "throwaway.json").write_text(
        json.dumps(cfg))
    mix = json.load(open(os.path.join(
        REPO, "chipbench", "traffic", "chat-decode.json")))
    mix["rate"] = 1.5
    (root / "chipbench" / "traffic" / "throwaway-mix.json").write_text(
        json.dumps(mix))
    (root / "chipbench" / "layer_metrics" / "throwaway.metric.py"
     ).write_text("SOURCE = 'program_counter'\n\n\ndef read(out):\n"
                  "    return 42.0\n")
    m["configs"].append({"name": "throwaway", "source": "x",
                         "file": "chipbench/configs/throwaway.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "throwaway.cell", "config": "throwaway",
                           "traffic": "throwaway-mix", "chips": 1,
                           "why": "test"})
    serve_e2e = next(e for e in m["end_to_end"]
                     if e["name"] == "ttft_p95_ms")
    serve_e2e["workloads"].append("throwaway.cell")
    m["per_layer"].append({"name": "throwaway.metric", "unit": "x",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "ttft_p95_ms",
                           "workloads": ["throwaway.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.load_cell("throwaway.cell", root=str(root))
    assert cell.traffic["rate"] == 1.5 and cell.config["name"] == "throwaway"
    assert [p["name"] for p in cell.per_layer] == ["throwaway.metric"]
    mod = manifest.layer_metric("throwaway.metric", root=str(root))
    assert mod.read({}) == 42.0


def test_unknown_traffic_driver_is_an_error():
    with pytest.raises(ValueError, match="unknown traffic driver"):
        manifest.driver("nonexistent")


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


def test_no_tpu_is_refused():
    with pytest.raises(run.NoChip, match="no TPU"):
        run.check_devices(1)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "smollm-bfly.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_without_a_tpu_prints_no_result():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_without_the_program_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(REPO, "chipbench"), tmp_path / "chipbench")
    shutil.copytree(os.path.join(REPO, "tests", "chipbench"),
                    tmp_path / "tests" / "chipbench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
