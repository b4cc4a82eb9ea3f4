"""The work counts and the peak table, against hand counts and decode_cost.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/test_work.py
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE), str(HERE / "family")]

import dense_decoder_program as family  # noqa: E402
import work  # noqa: E402
from repro.serve.costs import decode_cost  # noqa: E402

PEAK = work.PEAKS["TPU v5 lite"]


def conf(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# hand counts from the published config.json of each model
HAND = {
    # attention 576*9*64*2 + 576*3*64*2 = 884736; SwiGLU 3*576*1536 = 2654208
    "smollm-135m": {"layer": 884_736 + 2_654_208, "embed": 49152 * 576,
                    "kv": 30 * 2 * 3 * 64 * 2},
    # attention 6144*48*128*2 + 6144*4*128*2 = 81788928; GELU 2*6144*24576
    "starcoder2-15b-stage": {"layer": 81_788_928 + 301_989_888,
                             "embed": 49152 * 6144, "kv": 10 * 2 * 4 * 128 * 2},
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_hand_counts(name):
    dm = family.dims(conf(name))
    h = HAND[name]
    assert work.layer_params(dm) == h["layer"]
    assert work.kv_bytes_per_token(dm) == h["kv"]
    ctx = 100
    attn = 4 * dm["layers"] * dm["h"] * dm["dh"] * ctx
    assert work.flops_per_token(dm, ctx) == 2 * (dm["layers"] * h["layer"] + h["embed"]) + attn
    b, pos = 16, 99
    assert work.step_bytes(dm, b, pos) == (
        (dm["layers"] * h["layer"] + h["embed"]) * 2     # layers and head
        + b * dm["d"] * 2                                # embedding rows
        + b * pos * h["kv"] + b * h["kv"]                # cache read, write
        + b * dm["v"] * 2)                               # logits


def test_starcoder2_stage_is_bandwidth_bound():
    dm = family.dims(conf("starcoder2-15b-stage"))
    # 8.28 GB of weights (10 layers and the untied head in bf16), 16 x 401
    # cached positions of 20 KiB, 16 embedding rows and 16 rows of logits
    assert work.step_bytes(dm, 16, 400) == (
        8_279_556_096 + 16 * 401 * 20480 + 16 * 6144 * 2 + 16 * 49152 * 2)
    least = work.step_least_s(dm, 16, 400, PEAK)
    assert least == work.step_bytes(dm, 16, 400) / PEAK["hbm_bytes_per_s"]
    assert 10.2e-3 < least < 10.3e-3


@pytest.mark.parametrize("name", sorted(HAND))
def test_against_decode_cost(name):
    """Same work as serve/costs.py's decode_cost, less what it counts that a
    step does not need and plus what it leaves out."""
    c = conf(name)
    dm = family.dims(c)
    cfg = family.program_config(c)
    b, ctx = 8, 300
    ref = decode_cost(cfg, b, ctx)
    # operations: decode_cost counts the layers' parameters only
    head = dm["d"] * dm["v"]
    attn = 4 * dm["layers"] * dm["h"] * dm["dh"] * (ctx + 1)
    assert work.step_flops(dm, b, ctx) == pytest.approx(
        ref.flops + b * (2 * head + attn), rel=1e-12)
    # bytes: decode_cost reads an untied embedding table whole; a step
    # gathers one row per sequence
    table = 0 if dm["tied"] else head * 2
    assert work.step_bytes(dm, b, ctx) == pytest.approx(
        ref.bytes - table + b * dm["d"] * 2, rel=1e-12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_request_flops_sums_positions():
    dm = family.dims(conf("smollm-135m"))
    assert work.request_flops(dm, 3, 2) == sum(
        work.flops_per_token(dm, t) for t in (1, 2, 3, 4))
