"""The span recorder (core/tracing.py) and the spans the system records:
the serve engine's phases, the session API, the metadata raft and the
object store."""

import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import SPAN_NAMES, BoltSystem, Tracer, tracing
from repro.models.config import ModelConfig
from repro.models.lm import init_params
from repro.serve import ServeEngine, decode_response
from repro.streams import Producer, Topic

PHASES = ["serve.poll", "serve.prefill", "serve.decode", "serve.fetch",
          "serve.encode", "serve.append", "serve.ack", "serve.commit"]


def test_off_tracer_records_nothing_and_hands_back_one_object():
    t = Tracer()
    a = t.span("x", k=1)
    with a as inner:
        inner.lap(0)
        inner.set(k=2)
    assert a is t.span("y") is tracing.OFF and not a.on
    assert t.spans == [] and t.intervals == []


def test_nesting_sets_parents_and_keeps_attributes():
    t = Tracer()
    t.enable()
    with t.span("a", n=1) as a:
        with t.span("b"):
            with t.span("c") as c:
                c.set(bytes=3)
        with t.span("d"):
            pass
        a.set(m=2)
    with t.span("e"):
        pass
    got = [(s.name, s.parent, s.attrs) for s in t.spans]
    assert got == [("a", None, {"n": 1, "m": 2}), ("b", 0, {}),
                   ("c", 1, {"bytes": 3}), ("d", 0, {}), ("e", None, {})]
    a_, b_, c_ = t.spans[:3]
    assert a_.start <= b_.start <= c_.start <= c_.end <= b_.end <= a_.end
    assert a_[:3] == (a_.name, a_.start, a_.end)     # reads as (name, start, end)
    t.disable()
    with t.span("f"):
        pass
    assert len(t.spans) == 5
    assert len(t.intervals) == 1 and t.intervals[0][1] >= t.intervals[0][0]


def test_laps_and_drop():
    t = Tracer()
    t.enable()
    with t.span("loop") as s:
        for i in range(5, 9):
            s.lap(i)
    a = t.spans[0].attrs
    assert a["positions"] == 4 and a["host_max_at"] in range(5, 9)
    assert 0 <= a["host_max_s"] <= t.spans[0].end - t.spans[0].start
    assert set(a) == {"positions", "host_max_s", "host_max_at"}
    with t.span("empty") as s:
        with t.span("child"):
            pass
        s.drop()
    with t.span("after"):
        pass
    assert [s.name for s in t.spans] == ["loop", "after"]
    assert t.spans[1].parent is None


def test_annotate_factory_entered_once_per_span():
    entered, exited = [], []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    t = Tracer()
    t.enable(Ann)
    with t.span("a"):
        with t.span("b"):
            pass
    assert entered == ["a", "b"] and exited == ["b", "a"]
    t.enable()                   # no factory: spans are kept in memory only
    with t.span("c"):
        pass
    assert entered == ["a", "b"] and len(t.spans) == 3


def test_follow_leaves_an_enabled_tracer_on():
    t = Tracer()
    t.follow(False)
    assert not t.on
    t.follow(True)
    assert t.on
    t.follow(False)
    assert not t.on and len(t.intervals) == 1
    t.enable()
    t.follow(False)
    assert t.on


def test_core_imports_without_jax():
    code = ("import sys; import repro.core; from repro.core import tracing; "
            "assert 'jax' not in sys.modules, sorted(m for m in sys.modules "
            "if m.startswith('jax'))")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": str(Path(__file__).parents[1] / "src")},
                   timeout=120)


def test_log_spans_nest_under_the_session_api():
    system = BoltSystem(n_brokers=2, group_commit=4)
    log = system.create_log("l")
    system.tracer.enable()
    receipt = log.append_batch([b"a", b"b"])
    receipt.wait()
    names = [(s.name, s.parent) for s in system.tracer.spans]
    # staged by the append, PUT and proposed when the wait flushes
    assert names[0] == ("log.append", None)
    assert names[1] == ("log.wait", None)
    assert ("store.put", 1) in names and ("meta.propose", 1) in names
    assert {n for n, _ in names} <= set(SPAN_NAMES)


def _tiny_cfg():
    return ModelConfig(name="tiny", n_layers=2, d_model=32, n_heads=2,
                       n_kv_heads=1, d_ff=64, vocab_size=128,
                       tie_embeddings=True, attn_chunk=32)


def _serve(cfg, params, on, batches=2, gen=4):
    system = BoltSystem(n_brokers=3)
    req = Topic.create(system, "req")
    resp = Topic.create(system, "resp")
    prod = Producer(req)
    rng = np.random.default_rng(0)
    for rid in range(6):
        prod.produce({"id": f"r{rid}", "prompt": [
            int(t) for t in rng.integers(2, 128, 3 + rid)]})
    prod.flush()
    eng = ServeEngine(cfg, params, req, resp, batch_size=3)
    if on:
        system.tracer.enable()
    for _ in range(batches):
        assert eng.poll_and_serve(gen_tokens=gen) == 3
    assert eng.poll_and_serve(gen_tokens=gen) == 0      # an empty poll
    log = resp.log
    return system, decode_response(log.read(0, log.visible_tail))


def test_engine_records_one_batch_span_with_its_phases(monkeypatch):
    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    gen = 4
    system, served = _serve(cfg, params, on=True, gen=gen)
    spans = system.tracer.spans
    batches = [i for i, s in enumerate(spans) if s.name == "serve.batch"]
    assert len(batches) == 2             # the empty poll left no batch
    for n, b in enumerate(batches):
        s = spans[b]
        ids = [f"r{3 * n + k}" for k in range(3)]
        assert s.attrs == {"ids": ids, "size": 3, "batch_size": 3}
        kids = [i for i, c in enumerate(spans) if c.parent == b]
        assert [spans[i].name for i in kids] == PHASES
        by = {spans[i].name: spans[i] for i in kids}
        plen = 5 + 3 * n                 # the batch's longest (padded) prompt
        assert by["serve.prefill"].attrs["positions"] == plen
        assert by["serve.decode"].attrs["positions"] == gen - 1
        assert by["serve.decode"].attrs["host_max_at"] >= plen
        assert all(0 < c.end - c.start <= s.end - s.start
                   for c in by.values())
        under_append = [c.name for c in spans if c.parent is not None
                        and (c.parent == kids[5]
                             or spans[c.parent].parent == kids[5])]
        assert "log.append" in under_append and "store.put" in under_append
        assert [c.name for c in spans if c.parent == kids[6]] == ["log.wait"]
        # the cursor commit is a PUT of its own
        assert [c.name for c in spans if c.parent == kids[7]] == ["store.put"]

    # the same tokens with the tracer off, and not one span object made
    def no_span(*a, **k):
        raise AssertionError("a span was made with the tracer off")

    monkeypatch.setattr(tracing, "Span", no_span)
    system_off, served_off = _serve(cfg, params, on=False, gen=gen)
    assert served_off == served and len(served) == 6
    assert system_off.tracer.spans == []


def test_engine_follows_the_profiler(tmp_path):
    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    system = BoltSystem(n_brokers=2)
    req = Topic.create(system, "req")
    resp = Topic.create(system, "resp")
    eng = ServeEngine(cfg, params, req, resp, batch_size=2)
    prod = Producer(req)

    def batch():
        prod.produce({"id": "x", "prompt": [2, 3]})
        prod.flush()
        assert eng.poll_and_serve(gen_tokens=2) == 1

    batch()
    assert system.tracer.spans == []
    with jax.profiler.trace(str(tmp_path)):
        batch()
    assert [s.name for s in system.tracer.spans
            if s.parent is None] == ["serve.batch"]
    batch()                      # the session ended: recording stops
    assert [s.name for s in system.tracer.spans].count("serve.batch") == 1
    assert not system.tracer.on and len(system.tracer.intervals) == 1


@pytest.mark.parametrize("backend", ["memory", "file", "ranged", "tiered"])
def test_every_store_backend_puts_through_one_span(backend, tmp_path):
    system = BoltSystem(n_brokers=2, store_backend=backend,
                        store_root=str(tmp_path) if backend == "file" else None)
    system.tracer.enable()
    system.store.put("k", b"abc")
    assert system.store.get("k") == b"abc"
    assert [(s.name, s.attrs) for s in system.tracer.spans] == [
        ("store.put", {})]
