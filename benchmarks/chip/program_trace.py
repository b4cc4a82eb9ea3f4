#!/usr/bin/env python3
"""The program's own spans in traced runs of one cell, read beside the
harness's: where the device's idle time went.

    python3 benchmarks/chip/program_trace.py --workload W --seeds 1,2

Each seed makes a traced run, as ``run.py --trace 1`` makes it, whose trace is
reduced again with the program's span names (``repro.core.SPAN_NAMES``)
beside the harness's, so each idle gap of the device is named by the
innermost span the host was in. Prints the idle time by span, the share of it
under a program span (or under a harness span inside one), the readers of the
program's spans, and two checks: the eight ``serve.*`` phases' shares against
the harness's ``engine`` share of the same stretch, and ``queue_wait_p95_ms``
against the run's ``ttft_p95_ms``.

Stands in until the harness itself reduces the trace with the program's span
names (``breakdown.idle_gaps``). The runs share one process, so only the first
compiles; ``--seconds`` defaults to the benchmark's ``run_seconds``. One JSON
object per run on standard output.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (sets the cache directory and sys.path)

import jax  # noqa: E402

import harness  # noqa: E402
import program_spans  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core import SPAN_NAMES  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

PHASES = SPAN_NAMES[1:9]                       # the serve.batch's children
NESTED = {"req_poll", "req_commit", "log_append", "log_wait"}
READERS = ["prefill_host_share", "decode_host_share", "device_wait_share",
           "host_stall_ms", "queue_wait_p95_ms", "batch_occupancy",
           "device_idle", "step_ms"]


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _median_batch_s(batches) -> float:
    return statistics.median(b.end - b.start for b in batches)


def _free(run) -> None:
    run.engine = run.params = None
    run.close()
    gc.collect()


def _end_to_end(rec) -> dict:
    names = ["ttft_p95_ms"] if rec.latencies["ttft"] else ["tokens_per_s"]
    return {n: harness.reader(n)(rec) for n in names}


def split(conf, mix, family, seed: int, seconds: float) -> dict:
    run = harness.Run(conf, mix, family, seed, seconds, True, time.perf_counter())
    run.setup()
    run.window()
    rec = run.record()
    summary = trace_reduce.reduce(
        jax.profiler.ProfileData.from_serialized_xspace(run.xspace),
        tuple(harness.SPANS) + SPAN_NAMES)
    idle: dict = {}
    for name, ns in summary.gaps:
        n, s = idle.get(name, (0, 0.0))
        idle[name] = (n + 1, s + ns / 1e9)
    total = sum(s for _, s in idle.values())
    program = sum(s for name, (_, s) in idle.items()
                  if name in SPAN_NAMES or name in NESTED)
    stretches = program_spans.recorded(rec, run.system.tracer)
    length = sum(b - a for a, b in stretches)
    engine = sum(rec.spans.seconds({"engine"}, a, b) for a, b in stretches)
    phases = {p: program_spans.share(rec, {p}) for p in PHASES}
    laps = [(p.attrs["host_max_s"], p.name, p.attrs["host_max_at"],
             b.start - run.t_open)
            for b, under in program_spans.batches(rec)
            for name in program_spans.PHASES for p in under.get(name, ())]
    resumed = [b.start for b in run.batches if b.start > run._trace_span[1]]
    out = {"seed": seed,
           "idle_s": total, "idle_under_program_share":
               100.0 * program / total if total else None,
           "idle_gaps": {k: [n, s] for k, (n, s) in
                         sorted(idle.items(), key=lambda kv: -kv[1][1])},
           "recorded_s": length,
           "engine_share": 100.0 * engine / length if length else None,
           "phase_shares": phases,
           "phase_share_sum": sum(v or 0.0 for v in phases.values()),
           # the longest position: seconds, span, position, batch start
           "stall": max(laps, default=None),
           # from the profiler's stop to the next batch
           "profiler_stop_s": resumed[0] - run._trace_span[1] if resumed else None,
           **_end_to_end(rec),
           "readers": {n: harness.reader(n)(rec) for n in READERS},
           "batch_s_recorded": _median_batch_s(
               [b for b in run.batches
                if any(a <= b.start < e for a, e in stretches)] or run.batches),
           "batch_s_all": _median_batch_s(run.batches)}
    _free(run)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("program_trace.py reads the chip; JAX found no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    bench = json.loads((entry.ROOT / "BENCHMARK.json").read_text())
    _, conf, mix = entry.load(bench, args.workload)
    family, _ = entry.family(conf)
    seconds = args.seconds or bench["run_seconds"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        emit(dict(split(conf, mix, family, seed, seconds),
                  workload=args.workload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
