"""``correct`` of a serving cell at smoke size on the CPU: a sound run
passes, the same run with every decoded token altered where the engine
produces it fails, and the float8 control reads far above the limit the
program stays under."""

import io
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))
from chipbench_cells import smoke_cell  # noqa: E402

# smoke-size limits (the chip cells' live in chipbench/limits/): over the
# 58 served tokens of seeds 11-16, sound smoke runs read a widest gap of
# 0-0.017 and a mean gap of 0-0.00046; the float8 control 0.49-0.82 and
# 0.030-0.084
LIMITS = {"served_logit_gap": 0.1, "mean_logit_gap": 0.005}


def _run(cell, seed):
    from chipbench import run
    return run.run_cell(cell, seed, 2.0, False, backend="jnp",
                        limit_values=LIMITS)


@pytest.fixture(scope="module")
def cell():
    return smoke_cell("chat-decode")


def test_sound_run_is_correct(cell):
    res = _run(cell, 2 ** 33 + 5)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert list(res["checks"])[0] == "served_logit_gap"
    assert set(res["metrics"]) == {"setup_s", "ttft_p95_ms", "tpot_p95_ms",
                                   "gen_tok_s"}


def test_altered_tokens_are_not_correct(cell, monkeypatch):
    from repro.serve.engine import ServeEngine

    make = ServeEngine._decode_fn

    def altered(self):
        inner = make(self)

        def step(*args):
            nxt, caches = inner(*args)
            return (nxt + 1) % self.cfg.vocab_size, caches
        return step

    monkeypatch.setattr(ServeEngine, "_decode_fn", altered)
    res = _run(cell, 2 ** 33 + 5)
    assert not res["correct"]
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_float8_control_fails_where_the_program_passes(cell):
    from chipbench import calibrate

    rows = calibrate.limits(cell, [11, 12, 13], 2.0, backend="jnp",
                            out=io.StringIO(), limit_values=LIMITS)
    for r in rows:
        assert r["correct"], r["checks"]
        assert not r["control_correct"], r["control_checks"]
        assert r["control_logit_gap"] > LIMITS["served_logit_gap"]
        assert r["control_mean_gap"] > LIMITS["mean_logit_gap"]
