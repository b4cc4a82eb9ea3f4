"""The readers of the program's spans (``program_spans.py`` and the metrics
that use it), on a hand-built record and on a traced run at a tiny size.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/test_program_spans.py
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE), str(HERE / "family")]

import dense_decoder_program as family  # noqa: E402
import dense_decoder_reference as reference  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core import SpanRecord, Tracer  # noqa: E402
from test_harness import BENCH, CELL, MIX, TINY, cpu_peaks  # noqa: E402,F401

NEW = ["prefill_host_share", "decode_host_share", "device_wait_share",
       "host_stall_ms", "queue_wait_p95_ms", "batch_occupancy"]
T0 = -1000.0     # hand-built times lie where no live tracer's do


def _record(window, ttft):
    return harness.RunRecord(None, None, MIX, 10.0, 1, None, 0.0, window, [],
                             {"ttft": ttft, "response": ttft}, None, None)


def _batch(spans, t, ids, size_of, service):
    """A served batch at ``t``: poll 0.1 s, prefill 0.3, decode 0.5 (its
    slowest position 0.02 s), fetch 0.05, then append, ack with its
    log.wait, commit; ``service`` from the poll's end to the log.wait's."""
    b = len(spans)
    spans.append(SpanRecord("serve.batch", t, t + 1.0, None,
                            {"ids": ids, "size": len(ids),
                             "batch_size": size_of}))
    spans.append(SpanRecord("serve.poll", t, t + 0.1, b, {}))
    spans.append(SpanRecord("serve.prefill", t + 0.1, t + 0.4, b,
                            {"positions": 4, "host_max_s": 0.01,
                             "host_max_at": 1}))
    spans.append(SpanRecord("serve.decode", t + 0.4, t + 0.9, b,
                            {"positions": 2, "host_max_s": 0.02,
                             "host_max_at": 5}))
    spans.append(SpanRecord("serve.fetch", t + 0.9, t + 0.95, b, {}))
    spans.append(SpanRecord("serve.ack", t + 0.96, t + 0.99, b, {}))
    spans.append(SpanRecord("log.wait", t + 0.97, t + 0.1 + service,
                            len(spans) - 1, {}))


@pytest.fixture
def traced():
    """A tracer that recorded two batches of 8 (3 and 4 requests admitted)
    over [T0, T0 + 4]."""
    t = Tracer()
    _batch(t.spans, T0 + 1.0, ["r0", "r1", "r2"], 8, service=0.88)
    _batch(t.spans, T0 + 2.5, ["r3", "r4", "r5", "r6"], 8, service=0.88)
    t.intervals.append([T0, T0 + 4.0])
    return t


def test_shares_over_the_recorded_stretch(traced):
    rec = _record((T0 - 6.0, T0 + 10.0), [])
    read = {n: harness.reader(f"{n}.lat")(rec) for n in NEW}
    # two batches, each 0.3 s prefill, 0.5 s decode, 0.05 s fetch, over the
    # 2.5 s from the first one's start to the second one's end
    assert read["prefill_host_share"] == pytest.approx(100 * 0.6 / 2.5)
    assert read["decode_host_share"] == pytest.approx(100 * 1.0 / 2.5)
    assert read["device_wait_share"] == pytest.approx(100 * 0.1 / 2.5)
    assert read["host_stall_ms"] == pytest.approx(20.0)
    assert read["batch_occupancy"] == pytest.approx(100 * 7 / 16)
    assert read["queue_wait_p95_ms"] is None      # no latencies to read
    # the measured interval cuts the recorded stretch: the second batch out
    rec = _record((T0, T0 + 2.0), [])
    assert harness.reader("batch_occupancy.lat")(rec) == pytest.approx(
        100 * 3 / 8)
    assert harness.reader("prefill_host_share.lat")(rec) == pytest.approx(
        100 * 0.3 / 1.0)


def test_queue_wait_is_time_to_first_token_less_the_service(traced):
    ttft = [1.0, 1.5, 2.0, 0.9, 3.0, 1.2, 1.1]   # r0 .. r6
    rec = _record((T0, T0 + 4.0), ttft)
    waits = sorted(x - 0.88 for x in ttft)
    assert harness.reader("queue_wait_p95_ms.lat")(rec) == pytest.approx(
        waits[-1] * 1e3)


def test_readers_return_none_without_program_spans():
    rec = _record((T0 - 100.0, T0 - 90.0), [1.0])
    for n in NEW:
        assert harness.reader(f"{n}.lat")(rec) is None


def test_idle_gap_named_by_the_innermost_program_span():
    spans = [("engine", 0.0, 10.0), ("serve.batch", 0.5, 9.5),
             ("serve.decode", 2.0, 8.0), ("req_poll", 0.6, 0.9)]
    assert trace_reduce._innermost(spans, 5.0) == "serve.decode"
    assert trace_reduce._innermost(spans, 0.7) == "req_poll"
    assert trace_reduce._innermost(spans, 9.0) == "serve.batch"


def test_traced_open_loop_reports_every_new_metric():
    cells = [CELL]
    bench = dict(BENCH, per_layer=BENCH["per_layer"] + [
        {"name": f"{n}.lat", "unit": "%", "moves": "ttft_p95_ms",
         "workloads": cells} for n in NEW])
    out = harness.execute(bench, {"name": CELL}, TINY, MIX, family, reference,
                          5, 2.0, True, time.perf_counter())
    assert out["correct"], out["checks"]
    got = {n: out["metrics"][f"{n}.lat"]["value"] for n in NEW}
    assert all(v is not None for v in got.values()), got
    shares = got["prefill_host_share"] + got["decode_host_share"]
    assert 0 < got["device_wait_share"] and 0 < shares < 100
    assert 0 < got["batch_occupancy"] <= 100
    assert 0 < got["host_stall_ms"] and 0 < got["queue_wait_p95_ms"]
