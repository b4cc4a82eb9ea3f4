#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with a trace ``breakdown``, and last
``checks``: each number compared beside its limit, which also end standard
error. Off a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# JAX's persistent cache at a fixed path inside the checkout; the program's
# launch/cache.py takes the directory from this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT / "src"), str(HERE), str(HERE / "family")]


def load(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = json.loads((ROOT / conf_entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, conf, mix


def family(conf: dict):
    """The program adapter and the plain reference of the configuration's
    model family: ``family/<family>_program.py`` and ``_reference.py``."""
    return (importlib.import_module(f"{conf['family']}_program"),
            importlib.import_module(f"{conf['family']}_reference"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, conf, mix = load(bench, args.workload)

    import jax
    from repro.launch.cache import enable_compile_cache
    devices = jax.devices()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}", flush=True)
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    Path(cache_dir).mkdir(parents=True, exist_ok=True)

    import harness
    program, reference = family(conf)
    result = harness.execute(bench, cell, conf, mix, program, reference,
                             args.seed, args.seconds, bool(args.trace),
                             T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
