"""One run of one cell: the log-fed serving path, timed from the client's side.

Set-up builds a ``BoltSystem`` from the configuration's log deployment, makes
the weights on the device from the seed, and serves one warm-up batch of
every shape the cell's traffic will use. The window then drives
``ServeEngine.poll_and_serve`` over a ``requests`` and a ``responses`` topic:

* an open loop (``arrivals: poisson``) appends each request that fell due
  while the engine was busy before the next poll, stamped with its due time;
  latency counts from that due time. After the window the requests still
  outstanding are drained.
* a backlog (``arrivals: backlog``) keeps ``backlog_batches`` full batches
  queued; the batches that start inside the window are counted whole.

The engine appends its responses through :class:`AckedLog`, a proxy around
the responses log that stamps each record with the moment its append
receipt resolved: that is when a client could read it. After the window
every acknowledged record is read back through a client subscription, and a
sample of the served requests is compared with the plain reference
(:mod:`check`).
"""

from __future__ import annotations

import bisect
import gc
import math
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax
# The profiler session behind jax.profiler.start_trace, used directly: its
# stop() hands back the serialized trace, with no files written and no
# conversion to the trace viewer's JSON, which took tens of seconds inside
# the window for a few seconds of smollm-135m's steps (jax 0.9.0).
from jax._src.lib import _profiler

import check
import work
import trace_reduce
from compiles import CompileLog
from traffic import Request, Traffic

from repro.core import BoltSystem
from repro.serve import ServeEngine
from repro.streams import Topic
from repro.streams.records import decode_record, encode_record

SPANS = ("inject", "engine", "req_poll", "req_commit", "log_append", "log_wait",
         "idle", "readback")
DRAIN_LIMIT_S = 60.0        # an answer later than this past the window failed
TRACE_AT = 0.25             # traced run: start tracing this far into the window
TRACE_SECONDS = 6.0         # ... for at least this long (whole batches)
WARM_ID = 10 ** 6           # request ids of the warm-up batches start here
STEP_PROGRAM = "jit_decode_step"   # the program's decode step, as the trace names it


class Spans:
    """Host spans around the calls into each layer, kept in memory; in a
    traced run also written into the profiler's trace."""

    def __init__(self) -> None:
        self.items: List[Tuple[str, float, float]] = []
        self.traced = False

    @contextmanager
    def __call__(self, name: str):
        ann = jax.profiler.TraceAnnotation(name) if self.traced else None
        if ann:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.items.append((name, t0, time.perf_counter()))
            if ann:
                ann.__exit__(None, None, None)

    def wrap(self, name: str, fn):
        def spanned(*args, **kwargs):
            with self(name):
                return fn(*args, **kwargs)
        return spanned

    def seconds(self, names, lo: float, hi: float) -> float:
        return sum(max(0.0, min(b, hi) - max(a, lo))
                   for n, a, b in self.items if n in names)


class AckedReceipt:
    def __init__(self, receipt, records, owner: "AckedLog") -> None:
        self._receipt = receipt
        self._records = records
        self._owner = owner

    def wait(self):
        with self._owner.spans("log_wait"):
            self._receipt.wait()
        self._owner.acked(self._records, time.perf_counter())
        return self

    def __getattr__(self, name):
        return getattr(self._receipt, name)


class AckedLog:
    """Proxy around the log handle the engine appends its responses through:
    each record's acknowledgement time is taken where its receipt resolves."""

    def __init__(self, log, spans: Spans) -> None:
        self._log = log
        self.spans = spans
        self.records: Dict[tuple, dict] = {}     # (id, seq | "eos") -> record
        self.first_ack: Dict[str, float] = {}    # id -> first token acked
        self.eos_ack: Dict[str, float] = {}      # id -> EOS acked
        self.duplicates = 0
        self.last_ack = 0.0

    def append_batch(self, records):
        records = list(records)
        with self.spans("log_append"):
            receipt = self._log.append_batch(records)
        return AckedReceipt(receipt, records, self)

    def acked(self, records, t: float) -> None:
        self.last_ack = t
        for raw in records:
            rec = decode_record(raw)
            key = check.record_key(rec)
            self.duplicates += key in self.records
            self.records[key] = rec
            if rec.get("eos"):
                self.eos_ack.setdefault(rec["id"], t)
            else:
                self.first_ack.setdefault(rec["id"], t)

    def __getattr__(self, name):
        return getattr(self._log, name)


@dataclass
class Batch:
    prompt_lens: Tuple[int, ...]   # of the requests the engine took, in order
    start: float
    end: float
    acked: float = 0.0

    @property
    def size(self) -> int:
        return len(self.prompt_lens)

    @property
    def padded_len(self) -> int:
        """The prompt length the engine runs the batch at: it left-pads
        every prompt to the longest."""
        return max(self.prompt_lens)


@dataclass
class RunRecord:
    """What the metric readers read (``metrics/<name>.py``)."""
    conf: Dict
    dm: Dict
    mix: Dict
    seconds: float
    chips: int
    peak: Dict[str, float]
    setup_s: float
    window: Tuple[float, float]           # the measured interval, host clock
    batches: List[Batch]                  # counted: started inside the window
    latencies: Dict[str, List[float]]     # ttft / response seconds, per request
    spans: Spans
    compiles: CompileLog
    served_until: float = 0.0             # end of the last batch, drain included
    trace: Optional[trace_reduce.TraceSummary] = None
    traced_batches: List[Batch] = field(default_factory=list)

    @property
    def interval(self) -> float:
        return self.window[1] - self.window[0]


def _encode(req: Request, due_abs: float) -> bytes:
    return encode_record({"id": req.id, "prompt": req.prompt, "due": due_abs})


class Run:
    def __init__(self, conf: Dict, mix: Dict, family, seed: int,
                 seconds: float, traced: bool, t_process: float) -> None:
        self.conf, self.mix, self.family = conf, mix, family
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.t_process = t_process
        self.spans = Spans()
        self.compiles = CompileLog()
        self.cfg = family.program_config(conf)
        self.dm = family.dims(conf)
        self.traffic = Traffic(mix, seed, self.dm["v"])
        self.peak = work.peaks(jax.devices()[0].device_kind)

    # ------------------------------------------------------------- set-up
    def setup(self, warm: bool = True) -> None:
        log = self.conf["log"]
        self.store_root = tempfile.mkdtemp(prefix="bench-store-")
        self.system = BoltSystem(n_brokers=log["n_brokers"],
                                 n_meta_replicas=log["n_meta_replicas"],
                                 store_backend=log["store_backend"],
                                 store_root=self.store_root)
        self.params = self.family.make_params(self.conf, self.cfg, self.seed)
        jax.block_until_ready(self.params)
        if warm:
            self._warm()
        self.open_topics("requests", "responses")
        self.setup_s = time.perf_counter() - self.t_process

    def open_topics(self, requests: str, responses: str) -> None:
        """A request topic, a responses log behind the acknowledging proxy,
        and an engine between them."""
        self.requests = Topic.create(self.system, requests)
        self.resp_log = self.system.create_log(responses)
        self.acked_log = AckedLog(self.resp_log, self.spans)
        self.engine = ServeEngine(self.cfg, self.params, self.requests,
                                  Topic(responses, self.acked_log),
                                  batch_size=self.traffic.batch_size)
        # the engine's reads of the request log and its cursor commit
        consumer = self.engine.consumer
        consumer.poll = self.spans.wrap("req_poll", consumer.poll)
        consumer.commit = self.spans.wrap("req_commit", consumer.commit)

    def warm_shapes(self) -> List[Tuple[int, int]]:
        """(batch, prompt length) of every batch the window can run."""
        bs = self.traffic.batch_size
        sizes = range(1, bs + 1) if self.traffic.open_loop else [bs]
        return [(b, self.traffic.prompt_tokens) for b in sizes]

    def _warm(self) -> None:
        req = Topic.create(self.system, "warm-requests")
        resp = Topic.create(self.system, "warm-responses")
        eng = ServeEngine(self.cfg, self.params, req, resp,
                          batch_size=self.traffic.batch_size)
        for b, plen in self.warm_shapes():
            reqs = [Request(f"w{b}-{i}", self.traffic.prompt(WARM_ID + i, plen), 0.0)
                    for i in range(b)]
            req.log.append_batch([_encode(r, 0.0) for r in reqs]).wait()
            eng.poll_and_serve(gen_tokens=self.traffic.gen_tokens)

    # ------------------------------------------------------------- window
    def _serve_batch(self, prompts: List[List[int]]) -> Batch:
        """The engine's next batch, which takes the oldest ``prompts``."""
        start = time.perf_counter()
        with self.spans("engine"):
            # what the engine fails to answer, the log check counts
            self.engine.poll_and_serve(gen_tokens=self.traffic.gen_tokens)
        return Batch(tuple(map(len, prompts)), start, time.perf_counter(),
                     self.acked_log.last_ack)

    def _inject(self, reqs: List[Request], due_abs: List[float]) -> None:
        with self.spans("inject"):
            self.requests.log.append_batch(
                [_encode(r, d) for r, d in zip(reqs, due_abs)]).wait()

    def _maybe_trace(self, now: float) -> None:
        if not self.traced or self._trace_done:
            return
        if self._trace_ann is None and now >= self._trace_at:
            self._session = _profiler.ProfilerSession(_options())
            self._trace_ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._trace_ann.__enter__()
            self._trace_span = [now, None]
        elif self._trace_ann is not None and now >= self._trace_at + TRACE_SECONDS:
            self._stop_trace(now)

    def _stop_trace(self, now: float) -> None:
        if self._trace_ann is not None:
            self._trace_ann.__exit__(None, None, None)
            self._trace_span[1] = now
            self.xspace = self._session.stop()   # serialized, kept in memory
            self._trace_ann = None
            self._trace_done = True

    def window(self) -> None:
        self.spans.traced = self.traced
        self._trace_ann, self._trace_done = None, False
        self.batches: List[Batch] = []
        self.gc_pauses: List[Tuple[int, float, float]] = []
        gc.callbacks.append(self._gc_pause)
        self.t_open = time.perf_counter()
        self._trace_at = self.t_open + TRACE_AT * self.seconds
        try:
            if self.traffic.open_loop:
                self._open_loop()
            else:
                self._backlog()
        finally:
            gc.callbacks.remove(self._gc_pause)
        self._stop_trace(time.perf_counter())
        self.spans.traced = False
        self.t_closed = time.perf_counter()

    def _gc_pause(self, phase: str, info: Dict) -> None:
        """Collections of Python's garbage collector in the window:
        (generation, start, end)."""
        if phase == "start":
            self._gc_start = time.perf_counter()
        else:
            self.gc_pauses.append((info["generation"], self._gc_start,
                                   time.perf_counter()))

    def _open_loop(self) -> None:
        reqs = self.traffic.window(self.seconds)
        t0, end = self.t_open, self.t_open + self.seconds
        due = [t0 + r.due for r in reqs]
        self.due = {r.id: d for r, d in zip(reqs, due)}
        self.attempted = [r.id for r in reqs]
        self.prompts = {r.id: r.prompt for r in reqs}
        sent = served = 0
        while served < len(reqs):
            now = time.perf_counter()
            if now > end + DRAIN_LIMIT_S:
                break
            self._maybe_trace(now)
            j = bisect.bisect_right(due, now, lo=sent)
            if j > sent:
                self._inject(reqs[sent:j], due[sent:j])
                sent = j
            if sent > served:
                size = min(sent - served, self.traffic.batch_size)
                self.batches.append(self._serve_batch(
                    [r.prompt for r in reqs[served:served + size]]))
                served += size
            else:
                with self.spans("idle"):
                    time.sleep(max(0.0, due[sent] - time.perf_counter()))

    def _backlog(self) -> None:
        bs = self.traffic.batch_size
        keep = int(self.mix["backlog_batches"]) * bs
        self.attempted, self.prompts, self.due = [], {}, {}
        sent = served = 0
        end = self.t_open + self.seconds
        while time.perf_counter() < end:
            self._maybe_trace(time.perf_counter())
            if sent - served < keep:
                reqs = self.traffic.backlog(sent, keep - (sent - served))
                now = time.perf_counter()
                self._inject(reqs, [now] * len(reqs))
                self.prompts.update({r.id: r.prompt for r in reqs})
                sent += len(reqs)
            self.batches.append(self._serve_batch(
                [self.prompts[f"r{i}"] for i in range(served, served + bs)]))
            self.attempted += [f"r{i}" for i in range(served, served + bs)]
            served += bs

    # ------------------------------------------------------------ results
    def record(self) -> RunRecord:
        counted = [b for b in self.batches if b.start < self.t_open + self.seconds]
        lo = self.t_open if self.traffic.open_loop else counted[0].start
        hi = counted[-1].acked if counted else self.t_open + self.seconds
        # a request never answered counts as answered when the run gave up
        gave_up = self.t_closed
        lat = {"ttft": [], "response": []}
        for rid in self.attempted:
            due = self.due.get(rid)
            if due is None:
                continue
            lat["ttft"].append(self.acked_log.first_ack.get(rid, gave_up) - due)
            lat["response"].append(self.acked_log.eos_ack.get(rid, gave_up) - due)
        rec = RunRecord(self.conf, self.dm, self.mix, self.seconds,
                        len(jax.devices()), self.peak, self.setup_s,
                        (lo, max(hi, lo)), counted, lat, self.spans,
                        self.compiles, self.t_closed)
        if self.traced and self._trace_span[1] is not None:
            rec.trace = trace_reduce.reduce(
                jax.profiler.ProfileData.from_serialized_xspace(self.xspace), SPANS)
            ta, tb = self._trace_span
            rec.traced_batches = [b for b in self.batches
                                  if b.end > ta and b.start < tb]
        return rec

    def readback(self) -> List[dict]:
        with self.spans("readback"):
            sub = self.resp_log.subscribe(from_pos=0, follow=False)
            return [decode_record(raw) for batch in sub for raw in batch]

    def check(self, reference, quant: Optional[str] = None):
        """Read the log back, free the program's state so the reference has
        the chip, and compare the seed's sample with the reference. Returns
        (gaps of the served tokens, with ``quant`` the gaps of the tokens
        the control puts first, the log's counts, requests compared)."""
        back = self.readback()
        gen = self.traffic.gen_tokens
        counts = check.log_counts(self.acked_log.records, back, self.attempted,
                                  gen, self.acked_log.duplicates)
        served = check.served_tokens(back)
        answered = [r for r in self.attempted if len(served.get(r, ())) == gen]
        picked = check.sample(answered, self.prompts, gen, self.seed)
        self.engine = self.params = None
        gc.collect()
        sg, cg = check.gaps(reference, self.conf, self.seed,
                            [(self.prompts[r], served[r]) for r in picked], quant)
        return sg, cg, counts, len(picked)

    def close(self) -> None:
        shutil.rmtree(self.store_root, ignore_errors=True)


def traced_steps(rec: RunRecord) -> List[Tuple[int, int, float]]:
    """(batch, position, device ns) of each decode-step execution of the
    traced batches that lie whole inside the traced window."""
    t = rec.trace
    engine = sorted(s for s in t.spans if s[0] == "engine")
    if len(engine) != len(rec.traced_batches):
        raise RuntimeError(f"{len(engine)} traced engine spans for "
                           f"{len(rec.traced_batches)} batches")
    steps = t.executions(STEP_PROGRAM)
    gen = int(rec.mix["gen_tokens"])
    out = []
    for (_, a, b), batch in zip(engine, rec.traced_batches):
        ex = [m for m in steps if a <= m.start < b]
        n = batch.padded_len + gen - 1
        if a < t.window[0] or b > t.window[1] or len(ex) != n:
            continue
        out += [(batch.size, pos, m.end - m.start) for pos, m in enumerate(ex)]
    return out


def host_report(run: "Run") -> str:
    """Where the window's host time went, to find a stall: the slowest
    batches (start after the window's opening, size, seconds, and the
    seconds of it in the request log's poll and commit, the response
    append and wait, compiles and Python's garbage collector), the
    collections, and the compiles that started in the window."""
    def within(items, a, b):
        return sum(max(0.0, min(y, b) - max(x, a)) for x, y in items)

    def spans(*names):
        return [(x, y) for n, x, y in run.spans.items if n in names]

    parts = {"poll": spans("req_poll"), "commit": spans("req_commit"),
             "log": spans("log_append", "log_wait"),
             "compile": [(a, a + d) for _, a, d in run.compiles.events],
             "gc": [(x, y) for _, x, y in run.gc_pauses]}
    durs = sorted(b.end - b.start for b in run.batches)
    slow = sorted(run.batches, key=lambda b: b.start - b.end)[:3]
    by_gen = [sum(1 for g, _, _ in run.gc_pauses if g == k) for k in range(3)]
    compiled = run.compiles.started_in(run.t_open, run.t_closed)
    return (f"host: {len(durs)} batches, seconds median {durs[len(durs) // 2]!r} "
            f"max {durs[-1]!r}; slowest "
            + "; ".join(f"+{b.start - run.t_open:.3f}s x{b.size} {b.end - b.start:.4f}s ("
                        + ", ".join(f"{k} {within(v, b.start, b.end):.4f}"
                                    for k, v in parts.items()) + ")"
                        for b in slow)
            + f"; gc collections by generation {by_gen}, "
              f"{sum(y - x for x, y in parts['gc']):.4f}s, longest "
              f"{max((y - x for x, y in parts['gc']), default=0.0):.4f}s; "
              f"{len(compiled)} compiles in the window "
              f"{sorted({str(f) for f, _, _ in compiled})[:8]}")


def _options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the Python tracer costs ~20% of a step
    return opts


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


# ----------------------------------------------------------------- one run
def cell_metrics(bench: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or with
    a trace its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    here = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in here)]


def reader(name: str):
    """``metrics/<name>.py``; ``step_ms.lat`` and ``step_ms.tput`` share
    ``metrics/step_ms.py`` (the suffix names the end-to-end metric moved)."""
    import importlib.util
    base = name.split(".", 1)[0]
    path = Path(__file__).resolve().parent / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def readings(conf: Dict, mix: Dict, family, reference, seed: int,
             seconds: float, quant: Optional[str] = None):
    """A short window at the cell's own load, without warm-up, and what a
    run's check reads from it (:meth:`Run.check`)."""
    run = Run(conf, mix, family, seed, seconds, False, time.perf_counter())
    run.setup(warm=False)
    run.window()
    try:
        return run.check(reference, quant)
    finally:
        run.close()


def execute(bench: Dict, cell: Dict, conf: Dict, mix: Dict, family, reference,
            seed: int, seconds: float, traced: bool, t_process: float) -> Dict:
    """Set up, measure, check; returns the result line's object."""
    dev = jax.devices()[0]
    run = Run(conf, mix, family, seed, seconds, traced, t_process)
    run.setup()
    run.window()
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    rec = run.record()
    print(host_report(run), file=sys.stderr)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], traced):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    served_gap, _, counts, picked = run.check(reference)
    run.close()
    checks = {k: (v, 0) for k, v in counts.items()}
    # with no answer to compare, the gap is null and the run is not correct
    checks["logit_gap"] = (float(served_gap.max()) if served_gap.size else None,
                           conf["check"]["logit_gap_limit"])
    print(f"reference: {picked} requests, {served_gap.size} served tokens",
          file=sys.stderr)

    correct = all(v is not None and v <= lim for v, lim in checks.values())
    failed = checks["requests_unanswered"][0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_ns / 1e9
        device["window_s"] = rec.trace.window_ns / 1e9
    out = {"correct": bool(correct), "attempted": len(run.attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if rec.trace is not None:
        out["breakdown"] = trace_reduce.breakdown(rec.trace)
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    return out
