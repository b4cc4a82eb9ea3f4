#!/usr/bin/env python3
"""Readings made once, on the chip, to set what the benchmark holds fixed.

    python3 benchmarks/chip/calibrate.py sweep    --workload W --rates 4,6,8 --seconds 15
    python3 benchmarks/chip/calibrate.py readings --workload W --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 4
    python3 benchmarks/chip/calibrate.py padding  --config C --seeds 1,2 \
        --lengths 35,223,102,96,841,572,604,294 --gen 64
    python3 benchmarks/chip/calibrate.py record-trace --out DIR

* ``sweep``: one set-up, then the cell's open loop at each rate in turn, to
  find the knee (the highest rate without a growing backlog). The traffic
  file then holds 0.8 x the knee as a number.
* ``readings``: for each seed, a short window at the cell's own load and the
  reference's widest logit gap over the sample that a run compares; for the
  control seeds also the gap of the token the fp8 control puts first. The
  limit in the configuration file lies between the two (``PERF.md``).
* ``padding``: requests of the given prompt lengths served as one
  left-padded batch and each alone, both against the reference: the program
  fault that keeps varied-length mixes out of ``BENCHMARK.json``.
* ``record-trace``: a small traced run of a tiny configuration, kept as the
  recorded trace that ``test_trace_reduce.py`` reduces.

Each prints one JSON object per reading on standard output.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as entry  # noqa: E402  (sets the cache directory and sys.path)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
from repro.core import BoltSystem  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.streams import Topic  # noqa: E402
from traffic import Traffic  # noqa: E402


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def cell_files(name):
    bench = json.loads((entry.ROOT / "BENCHMARK.json").read_text())
    return entry.load(bench, name)


def sweep(args) -> None:
    _, conf, mix = cell_files(args.workload)
    family, _ = entry.family(conf)
    run = harness.Run(conf, mix, family, args.seed, args.seconds, False,
                      T_PROCESS)
    run.setup()
    for rate in [float(r) for r in args.rates.split(",")]:
        run.traffic = Traffic(dict(mix, rate_per_s=rate), args.seed,
                              run.dm["v"])
        run.open_topics(f"requests-{rate}", f"responses-{rate}")
        run.window()
        lat = run.record().latencies
        sizes = [b.size for b in run.batches]
        emit({"rate_per_s": rate, "requests": len(run.attempted),
              "batches": len(sizes), "mean_batch": float(np.mean(sizes)),
              "batch_s": float(np.mean([b.end - b.start for b in run.batches])),
              "drain_s": run.batches[-1].end - (run.t_open + args.seconds),
              "ttft_p50_ms": harness.percentile(lat["ttft"], 50) * 1e3,
              "ttft_p95_ms": harness.percentile(lat["ttft"], 95) * 1e3})


def readings(args) -> None:
    _, conf, mix = cell_files(args.workload)
    family, reference = entry.family(conf)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        sg, cg, counts, _ = harness.readings(
            conf, mix, family, reference, seed, args.seconds,
            "fp8" if seed in controls else None)
        emit({"workload": args.workload, "seed": seed, "tokens": int(sg.size),
              "logit_gap": float(sg.max()),
              "logit_gap_p99": float(np.quantile(sg, 0.99)),
              "control_gap": float(cg.max()) if cg.size else None,
              "control_tokens_off": int((cg > 0).sum()) if cg.size else None,
              "served_tokens_off": int((sg > 0).sum()),
              "seconds": time.perf_counter() - t0, **counts})


def padding(args) -> None:
    conf = json.loads((entry.HERE / "configs" / f"{args.config}.json").read_text())
    family, reference = entry.family(conf)
    cfg = family.program_config(conf)
    lens = [int(n) for n in args.lengths.split(",")]
    mix = {"arrivals": "backlog", "prompt": {"dist": "fixed", "tokens": max(lens)},
           "gen_tokens": args.gen, "batch_size": len(lens)}
    for seed in [int(s) for s in args.seeds.split(",")]:
        traffic = Traffic(mix, seed, family.dims(conf)["v"])
        prompts = {f"r{i}": traffic.prompt(i, n) for i, n in enumerate(lens)}
        params = family.make_params(conf, cfg, seed)
        system = BoltSystem(n_brokers=4, store_backend="memory")
        served = {}
        for name, bs, ids in (("batch", len(lens), list(prompts)),
                              ("alone", 1, list(prompts)[:args.singles])):
            req = Topic.create(system, f"req-{name}")
            resp = Topic.create(system, f"resp-{name}")
            eng = ServeEngine(cfg, params, req, resp, batch_size=bs)
            req.log.append_batch([json.dumps({"id": r, "prompt": prompts[r]}).encode()
                                  for r in ids]).wait()
            while eng.poll_and_serve(gen_tokens=args.gen):
                pass
            served[name] = check.served_tokens(
                [json.loads(x) for x in resp.log.read(0, resp.log.visible_tail)])
        del params, eng
        for name, got in served.items():
            for rid, toks in sorted(got.items()):
                sg, _ = check.gaps(reference, conf, seed, [(prompts[rid], toks)])
                emit({"config": args.config, "seed": seed, "served": name,
                      "id": rid, "prompt": len(prompts[rid]),
                      "left_pad": max(lens) - len(prompts[rid]) if name == "batch" else 0,
                      "logit_gap": float(sg.max()),
                      "tokens_off": int((sg > 0).sum())})


def record_trace(args) -> None:
    sys.path.insert(0, str(entry.HERE))
    from test_harness import MIX, TINY
    family, _ = entry.family(TINY)
    harness.TRACE_AT, harness.TRACE_SECONDS = 0.2, 0.3
    run = harness.Run(TINY, MIX, family, 5, 1.0, True, T_PROCESS)
    run.setup()
    run.window()
    rec = run.record()
    with open(args.out, "wb") as f:
        f.write(run.xspace)
    emit({"trace": args.out, "traced_batches": [
        [b.size, b.padded_len] for b in rec.traced_batches],
        "busy_s": rec.trace.busy_ns / 1e9, "window_s": rec.trace.window_ns / 1e9})
    run.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=15.0)
    s.add_argument("--seed", type=int, default=1)
    s = sub.add_parser("readings")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--control-seeds", default="")
    s.add_argument("--seconds", type=float, default=4.0)
    s = sub.add_parser("padding")
    s.add_argument("--config", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--lengths", required=True)
    s.add_argument("--gen", type=int, default=64)
    s.add_argument("--singles", type=int, default=8)
    s = sub.add_parser("record-trace")
    s.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py reads the chip; JAX found no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    {"sweep": sweep, "readings": readings, "padding": padding,
     "record-trace": record_trace}[args.cmd](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
