"""Smoke-size cells of the benchmark for the CPU: the smoke variants of
the SmolLM configurations under the benchmark's own traffic files, with
lengths and slots cut to what a test can run in the jnp backend."""

import json
import os
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

SMOKE_SIZES = dict(hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, vocab_size=512)


def _json(*parts):
    with open(os.path.join(REPO, "chipbench", *parts)) as f:
        return json.load(f)


def smoke_cell(traffic: str, butterfly: bool = True):
    from chipbench import manifest

    cfg = _json("configs", "smollm-135m-butterfly.json" if butterfly
                else "smollm-135m.json")
    cfg.update(SMOKE_SIZES, registry=cfg["registry"] + "-smoke")
    mix = _json("traffic", traffic + ".json")
    if mix["driver"] == "train":
        mix["trainer"].update(seq_len=64, batch=4)
        e2e = ["setup_s", "train_tok_s"]
    else:
        mix.update(rate=4.0,
                   prompt=dict(mix["prompt"], median=12, min=4, max=40),
                   output=dict(mix["output"], median=6, min=3, max=12),
                   engine=dict(mix["engine"], slots=4, max_len=64,
                               prefill_chunk=16),
                   reference={"min_tokens": 20, "max_requests": 64},
                   drain_s=60.0)
        e2e = ["setup_s", "ttft_p95_ms", "tpot_p95_ms", "gen_tok_s"]
    return manifest.Cell(name="smoke." + traffic, chips=1, config=cfg,
                         traffic=mix,
                         end_to_end=[{"name": n, "unit": "x"} for n in e2e],
                         per_layer=[])
