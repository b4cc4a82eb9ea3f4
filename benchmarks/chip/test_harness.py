"""A whole run at a tiny size on the CPU, sound and with the timed path broken.

    PYTHONPATH=src python -m pytest -q benchmarks/chip

Each test drives ``harness.execute`` as ``run.py`` does, minus its look for a
chip, and reads ``correct``: an open loop (also traced) and an offline
backlog, sound; then the open loop with a fault planted in the program under
the harness: a token altered where the engine produces it, half of each
batch left unanswered, and a decode step that hands back its caches
unchanged.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE), str(HERE / "family")]

import jax  # noqa: E402

import dense_decoder_program as family  # noqa: E402
import dense_decoder_reference as reference  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402
from repro.streams import Consumer  # noqa: E402

CELL = "tiny-agent"
# A smoke-sized configuration of the smollm-135m family: every width cut.
TINY = {
    "name": "tiny", "family": "dense_decoder", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_word_embeddings": True, "vocab_size": 512,
    "serve_dtype": "bfloat16",
    "log": json.loads((HERE / "configs" / "smollm-135m.json").read_text())["log"],
    # between the tiny model's sound runs (widest gap at most 0.009 on seeds
    # 11-16) and its fp8 control (at least 0.147): test_control.py
    "check": {"logit_gap_limit": 0.08},
}
MIX = {"arrivals": "poisson", "rate_per_s": 40.0, "block_s": 0.5,
       "prompt": {"dist": "fixed", "tokens": 12}, "template_tokens": 4,
       "gen_tokens": 8, "batch_size": 4}
BENCH = {
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "ttft_p95_ms", "unit": "ms", "workloads": [CELL]},
        {"name": "response_p95_ms", "unit": "ms", "workloads": [CELL]},
    ],
    "per_layer": [
        {"name": "log_ack_share.lat", "unit": "%", "moves": "response_p95_ms"},
        {"name": "compiles_in_window.lat", "unit": "count",
         "moves": "ttft_p95_ms"},
        {"name": "mfu.lat", "unit": "%", "moves": "ttft_p95_ms"},
    ],
}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(work.PEAKS, kind, work.PEAKS["TPU v5 lite"])


def run(tmp_path, seed=3, traced=False, conf=TINY):
    import time
    return harness.execute(BENCH, {"name": CELL}, conf, MIX, family,
                           reference, seed, 2.0, traced, time.perf_counter())


def test_sound_run_is_correct(tmp_path):
    out = run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] == int(MIX["rate_per_s"] * 2.0)
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "ttft_p95_ms", "response_p95_ms"}
    assert list(out)[-1] == "checks"
    assert out["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_per_layer(tmp_path):
    out = run(tmp_path, traced=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["compiles_in_window.lat"]["value"] == 0.0
    assert 0 < out["metrics"]["mfu.lat"]["value"] < 100
    assert "log_ack_share.lat" in out["metrics"]


def test_batch_counts_each_prompt_and_runs_at_the_longest():
    from types import SimpleNamespace
    b = harness.Batch((12, 7, 9), 0.0, 1.0)
    assert (b.size, b.padded_len) == (3, 12)
    dm = family.dims(TINY)
    peak = work.PEAKS["TPU v5 lite"]
    rec = SimpleNamespace(batches=[b], interval=1.0, chips=1, mix=MIX, dm=dm,
                          peak=peak)
    useful = sum(work.request_flops(dm, n, MIX["gen_tokens"]) for n in (12, 7, 9))
    assert harness.reader("mfu.lat")(rec) == pytest.approx(
        100.0 * useful / peak["flops"])


def test_offline_backlog_run(tmp_path):
    mix = {"arrivals": "backlog", "backlog_batches": 2,
           "prompt": {"dist": "fixed", "tokens": 12}, "gen_tokens": 8,
           "batch_size": 4}
    bench = {"end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
             "per_layer": []}
    import time
    out = harness.execute(bench, {"name": CELL}, TINY, mix, family, reference,
                          3, 2.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] % 4 == 0 and out["attempted"] >= 4
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def test_altered_token_fails(tmp_path, monkeypatch):
    real = engine_mod.ServeEngine._greedy
    monkeypatch.setattr(engine_mod.ServeEngine, "_greedy",
                        lambda self, logits: (real(self, logits) + 1) % 500)
    out = run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY["check"]["logit_gap_limit"]


def test_half_the_batch_left_out_fails(tmp_path, monkeypatch):
    real = Consumer.poll

    def half(self, max_records=256):
        recs = real(self, max_records)
        return recs[:max(1, len(recs) // 2)] if self.group == "serve" else recs

    monkeypatch.setattr(Consumer, "poll", half)
    out = run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["requests_unanswered"]["value"] > 0


def test_state_left_unchanged_fails(tmp_path, monkeypatch):
    real = engine_mod._decode_step

    def stale(cfg, params, caches, tokens, pos):
        logits, _ = real(cfg, params, caches, tokens, pos)
        return logits, caches

    monkeypatch.setattr(engine_mod, "_decode_step", stale)
    out = run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > TINY["check"]["logit_gap_limit"]
