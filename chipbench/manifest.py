"""Finds everything a cell needs by name, so that a new configuration,
traffic mix or per-layer metric is a new file and a new entry in
``BENCHMARK.json``, never an edit:

* ``BENCHMARK.json`` at the checkout's root: the cells and metrics;
* ``chipbench/configs/<config>.json``: one model configuration;
* ``chipbench/traffic/<traffic>.json``: one traffic mix, whose ``driver``
  names the general driver that runs it, ``chipbench/<driver>_driver.py``
  (``serve`` or ``train`` today);
* ``chipbench/layer_metrics/<metric>.py``: one per-layer metric's reader,
  which declares its ``SOURCE``, ``applies(cell)`` (whether the cell's
  runs give it anything to read) and ``read(out)`` (``None`` when a run
  gives nothing).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(known: {[e['name'] for e in entries]})")


@dataclasses.dataclass
class Cell:
    """One workload with everything resolved from its names."""

    name: str
    chips: int
    config: Dict                   # the configuration file's contents
    traffic: Dict                  # the traffic file's contents
    end_to_end: List[Dict]         # the e2e metrics this cell reports
    per_layer: List[Dict]          # the per-layer metrics this cell reports


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              manifest: Optional[Dict] = None) -> Cell:
    m = manifest if manifest is not None else load_manifest(root)
    w = _by_name(m["workloads"], name, "workload")
    c = _by_name(m["configs"], w["config"], "config")
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [e for e in m["end_to_end"] if _reports(e, name)]
    reported = {e["name"] for e in e2e}
    per = [p for p in m["per_layer"]
           if _reports(p, name) and p["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per)


def layer_metric(name: str, root: str = ROOT):
    """The reader module of one per-layer metric (its file name is the
    metric's name, dots included)."""
    path = os.path.join(root, "chipbench", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    """The driver module of a traffic mix's ``driver`` kind."""
    if not os.path.exists(os.path.join(HERE, kind + "_driver.py")):
        raise ValueError(f"unknown traffic driver {kind!r}")
    return importlib.import_module(f"chipbench.{kind}_driver")


def model_config(config: Dict, backend: Optional[str] = None):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry with the file's ``program`` overrides, checked against
    the sizes the file states."""
    from repro.configs import registry

    cfg = registry.get(config["registry"])
    prog = dict(config.get("program", {}))
    be = backend or prog.pop("backend", None)
    prog.pop("backend", None)
    cfg = cfg.with_(**prog)
    if cfg.butterfly is not None and be is not None:
        cfg = cfg.with_(butterfly=dataclasses.replace(cfg.butterfly,
                                                      backend=be))
    want = {"hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim_, "vocab_size": cfg.vocab_size,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": cfg.tie_embeddings}
    wrong = {k: (config[k], v) for k, v in want.items() if config[k] != v}
    if wrong:
        raise ValueError(f"the program's {config['registry']!r} does not "
                         f"match {config['name']}.json: {wrong} "
                         f"(file, program)")
    bfly = config.get("butterfly")
    if (bfly is None) != (cfg.butterfly is None) or (
            bfly and (tuple(bfly["sites"]) != tuple(cfg.butterfly.sites)
                      or bfly["k_factor"] != cfg.butterfly.k_factor
                      or bfly["seed"] != cfg.butterfly.seed)):
        raise ValueError(f"butterfly sites of {config['registry']!r} do "
                         f"not match {config['name']}.json")
    return cfg
