#!/usr/bin/env python3
"""Compile each cell's decode and prefill programs for a described TPU
v5e, without a chip, and print what the compiler says they ask of HBM.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--config NAME] [--pages N]

For every configuration in ``BENCHMARK.json`` (or the one named): the
fused decode program (``decode_chunk`` steps over the engine's slots and
page pool) and the widest prefill quantum the mixes make (one slot,
``prefill_quantum`` tokens at the deepest offset), each compiled through
the Pallas kernels for one chip of a ``v5e:2x2`` topology.  Nothing runs;
the compiler refuses a program that does not fit.  ``--pages`` overrides
the pool's page count, to size it.  Used to size the pools and slots of
the configuration files before any chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config")
    ap.add_argument("--pages", type=int)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import registry
    from repro.model import transformer as tf
    from repro.model.layers import Runtime

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for entry in spec["configs"]:
        if args.config and entry["name"] != args.config:
            continue
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        e = config["engine"]
        pages = args.pages or e["num_pages"]
        cfg = registry.arch(config, where=entry["file"]).model_config(config)
        rt = Runtime(attn_impl="pallas", interpret=False,
                     param_dtype=jnp.bfloat16,
                     activation_dtype=jnp.bfloat16)
        place = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
        params = place(jax.eval_shape(lambda k: tf.init(cfg, k, rt)[0],
                                      jax.random.PRNGKey(0)))
        caches = place(jax.eval_shape(lambda: tf.init_paged_cache(
            cfg, e["slots"], {"full": pages}, e["page_size"],
            jnp.bfloat16)))
        slots, width = e["slots"], e["max_len"] // e["page_size"]
        tables = {"full": s((slots, width), jnp.int32)}

        def loop(params, caches, kv_len, logits, remaining, key, tables):
            return tf.decode_loop(cfg, params, caches, kv_len, logits,
                                  remaining, key, n_steps=e["decode_chunk"],
                                  rt=rt, block_tables=tables)

        q = e["prefill_quantum"]
        off = (e["max_len"] - 1) // q * q

        def prefill(params, tokens, caches, tables, slot_ids, true_len,
                    cached_len):
            return tf.prefill(cfg, params, {"inputs": tokens}, caches, rt,
                              kv_offset=off, true_len=true_len,
                              block_tables=tables, slot_ids=slot_ids,
                              cached_len=cached_len)

        programs = {
            f"decode ({e['decode_chunk']} steps, {slots} slots, "
            f"{pages} pages)": jax.jit(
                loop, donate_argnums=(1, 2, 3, 4, 5)).lower(
                params, caches, s((slots,), jnp.int32),
                s((slots, cfg.vocab), jnp.float32), s((slots,), jnp.int32),
                s((2,), jnp.uint32), tables),
            f"prefill (1 x {q} tokens at offset {off})": jax.jit(
                prefill, donate_argnums=(2,)).lower(
                params, s((1, q), jnp.int32), caches, tables,
                s((1,), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32)),
        }
        for name, lowered in programs.items():
            m = lowered.compile().memory_analysis()
            print(f"{entry['name']}: {name}: arguments "
                  f"{m.argument_size_in_bytes / GIB:.2f} GiB, temporaries "
                  f"{m.temp_size_in_bytes / GIB:.2f} GiB, outputs "
                  f"{m.output_size_in_bytes / GIB:.2f} GiB, aliased "
                  f"{m.alias_size_in_bytes / GIB:.2f} GiB; arguments + "
                  f"temporaries {(m.argument_size_in_bytes + m.temp_size_in_bytes) / GIB:.2f} GiB",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
