#!/usr/bin/env python3
"""Compile a serving cell's prefill and decode programs for a described
TPU v5e, without a chip, and print their memory analysis.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

It builds the cell's configuration at its stated widths, as the
configuration's model family makes its ``ArchConfig``, and compiles, for
one chip of a described ``v5e:2x2``, the program's jitted prefill at every
prompt length of the cell's traffic and its decode step over one decode
PE's slot bank at the cell's ``max_len``, from shapes only.  What the chip's
compiler refuses here costs no chip time; a compile that passes says
nothing about results or times.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import registry
    from repro.models import kvcache, model

    jax.config.update("jax_enable_compilation_cache", False)
    reg = registry.Registry(ROOT)
    wl = reg.workload(args.workload)
    cfg = reg.config(wl["config"])
    arch = reg.family(cfg["family"]).arch_config(cfg)
    fl = wl["fleet"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), arch)))
    prefill = jax.jit(lambda p, b, c: model.prefill(p, arch, b, c))
    decode = jax.jit(lambda p, t, pos, c: model.decode_step(p, arch, t, pos,
                                                            c))
    GB = 1e9

    def report(what, compiled):
        m = compiled.memory_analysis()
        print(f"{what}: arguments {m.argument_size_in_bytes / GB:.3f} GB, "
              f"outputs {m.output_size_in_bytes / GB:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / GB:.3f} GB, generated code "
              f"{m.generated_code_size_in_bytes / 1e6:.3f} MB", flush=True)

    for S in reg.traffic(wl["traffic"])["prompt_lens"]:
        batch = {"tokens": jax.ShapeDtypeStruct((1, S), jnp.int32,
                                                sharding=one)}
        cache = sds(kvcache.cache_struct(arch, 1, fl["max_len"]))
        report(f"prefill B=1 S={S}",
               prefill.lower(params, batch, cache).compile())
    B = fl["num_slots"]
    cache = sds(kvcache.cache_struct(arch, B, fl["max_len"]))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
    report(f"decode step, bank of {B} slots, max_len {fl['max_len']}",
           decode.lower(params, tok, pos, cache).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
