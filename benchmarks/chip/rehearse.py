#!/usr/bin/env python3
"""Rehearse a chip call on the CPU, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 benchmarks/chip/rehearse.py

1. The whole harness at a smoke size: the tests of ``test_harness.py`` (a
   sound open-loop run, a traced run, an offline backlog run, and the three
   planted faults).
2. Each cell's programs at their real sizes, compiled for one chip of a
   described ``v5e:2x2`` TPU: the seeded weight maker and the decode step at
   the cell's batch and cache length. Prints ``memory_analysis()`` of each.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE), str(HERE / "family")]


def smoke() -> int:
    import pytest
    return pytest.main(["-q", "-p", "no:cacheprovider",
                        str(HERE / "test_harness.py")])


def compile_cells() -> None:
    import json
    from functools import partial

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import dense_decoder_program as family
    import dense_decoder_weights as W
    from repro.models.lm import decode_step, init_caches

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    confs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
             for c in bench["configs"]}
    for cell in bench["workloads"]:
        conf = confs[cell["config"]]
        mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
        cfg = family.program_config(conf)
        dm = W.dims(conf)
        batch = mix["batch_size"]
        length = mix["prompt"]["tokens"] + mix["gen_tokens"]
        shaped = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)
        key = jax.eval_shape(lambda: W.root_key(0))
        make = jax.jit(partial(family._params, dm, cfg.padded_vocab))
        params = shaped(jax.eval_shape(make, key))
        caches = shaped(jax.eval_shape(lambda: init_caches(cfg, batch, length)))
        tok = jax.ShapeDtypeStruct((batch, 1), jax.numpy.int32, sharding=chip)
        pos = jax.ShapeDtypeStruct((), jax.numpy.int32, sharding=chip)
        for what, lowered in (
                ("weights", make.lower(shaped(key))),
                ("decode_step", jax.jit(partial(decode_step, cfg)).lower(
                    params, caches, tok, pos))):
            mem = lowered.compile().memory_analysis()
            print(f"{cell['name']} {what} (batch {batch}, cache {length}): "
                  f"arguments {mem.argument_size_in_bytes}, outputs "
                  f"{mem.output_size_in_bytes}, temporaries "
                  f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes}",
                  flush=True)


if __name__ == "__main__":
    rc = smoke()
    compile_cells()
    sys.exit(rc)
