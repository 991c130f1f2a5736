"""Compile-only rehearsals for a described TPU v5e (no chip needed).

The main-path Pallas kernels at stablelm-1.6b widths (MLA at
deepseek-v3's latent widths), for bf16 and quantized pools, plus one whole
stablelm-1.6b ``decode_loop`` step at the pool size ``chip_smoke.py``
serves.  Nothing runs: the TPU compiler refuses what the chip would refuse
(block tiling, VMEM, HBM), and a kernel that compiled shows up in the
program text as ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler library.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops

B, H, E, T, PS = 8, 32, 64, 2048, 16     # stablelm-1.6b decode batch
W = T // PS
P = B * W
R, RD, HM = 512, 64, 128                 # deepseek-v3 MLA latent widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise write its logs outside the
        # checkout
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_POOL = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


@pytest.mark.parametrize("pool", ["bf16", "int8", "fp8"])
def test_paged_gqa_decode_compiles(one_chip, pool):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    dt = _POOL[pool]
    args = [s((B, H, 1, E), jnp.bfloat16), s((P, H, PS, E), dt),
            s((P, H, PS, E), dt), s((B, W), jnp.int32), s((B,), jnp.int32)]
    if pool != "bf16":
        args += [s((P, H, PS), jnp.float16)] * 2

    def fn(q, k, v, bt, kv_len, *scales):
        ks, vs = scales if scales else (None, None)
        return ops.fusemax_decode_paged(q, k, v, bt, kv_len, impl="pallas",
                                        interpret=False, k_scale=ks,
                                        v_scale=vs)

    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_paged_verify_chain_compiles(one_chip):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    fn = lambda q, k, v, bt, kv_len: ops.fusemax_decode_paged(
        q, k, v, bt, kv_len, impl="pallas", interpret=False)
    compiled = _compile(fn, s((B, H, 5, E), jnp.bfloat16),
                        s((P, H, PS, E), jnp.bfloat16),
                        s((P, H, PS, E), jnp.bfloat16),
                        s((B, W), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_paged_mla_decode_compiles(one_chip, pool):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    dt = _POOL[pool]
    args = [s((B, HM, 1, R + RD), jnp.bfloat16), s((P, PS, R), dt),
            s((P, PS, RD), dt), s((B, W), jnp.int32), s((B,), jnp.int32)]
    if pool != "bf16":
        args += [s((P, PS), jnp.float16)] * 2

    def fn(q, ckv, krope, bt, kv_len, *scales):
        cs, ks = scales if scales else (None, None)
        return ops.fusemax_mla_decode_paged(
            q, ckv, krope, bt, kv_len, impl="pallas", interpret=False,
            ckv_scale=cs, krope_scale=ks)

    assert "tpu_custom_call" in _compile(fn, *args).as_text()


def test_prefill_compiles(one_chip):
    s = _shape(one_chip, (1, H, T, E), jnp.bfloat16)
    fn = lambda q, k, v: ops.fusemax_attention(
        q, k, v, causal=True, impl="pallas", interpret=False)
    assert "tpu_custom_call" in _compile(fn, s, s, s).as_text()


def test_dense_decode_compiles(one_chip):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    fn = lambda q, k, v, kv_len: ops.fusemax_decode(
        q, k, v, kv_len, impl="pallas", interpret=False)
    compiled = _compile(fn, s((B, H, 1, E), jnp.bfloat16),
                        s((B, H, T, E), jnp.bfloat16),
                        s((B, H, T, E), jnp.bfloat16), s((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def test_stablelm_decode_loop_fits_one_chip(one_chip):
    """One fused decode dispatch of stablelm-1.6b in bf16 through the
    Pallas kernels, at ``chip_smoke.py``'s pool (8 slots x 1024 tokens):
    the compiler fits it in one v5e's HBM (it raises otherwise)."""
    from repro.configs import get_config
    from repro.model import transformer as tf
    from repro.model.layers import Runtime

    cfg = get_config("stablelm-1.6b")
    rt = Runtime(attn_impl="pallas", interpret=False,
                 param_dtype=jnp.bfloat16, activation_dtype=jnp.bfloat16)
    slots, max_len = 8, 1024
    place = lambda tree: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(lambda k: tf.init(cfg, k, rt)[0],
                                  jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(lambda: tf.init_paged_cache(
        cfg, slots, {"full": slots * max_len // PS}, PS, jnp.bfloat16)))
    s = lambda shape, dt: _shape(one_chip, shape, dt)

    def loop(params, caches, kv_len, logits, remaining, key, tables):
        return tf.decode_loop(cfg, params, caches, kv_len, logits,
                              remaining, key, n_steps=16, rt=rt,
                              block_tables=tables)

    compiled = jax.jit(loop, donate_argnums=(1, 2, 3, 4, 5)).lower(
        params, caches, s((slots,), jnp.int32),
        s((slots, cfg.vocab), jnp.float32), s((slots,), jnp.int32),
        s((2,), jnp.uint32), {"full": s((slots, max_len // PS), jnp.int32)},
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes + mem.output_size_in_bytes)
    assert live < 16e9, mem


@pytest.mark.parametrize("kernel", ["fusemax_decode_paged",
                                    "fusemax_mla_decode_paged",
                                    "fusemax_attention"])
def test_kernel_name_names_the_compiled_instruction(one_chip, kernel):
    """``pallas_call(name=...)`` becomes the name of the custom call's
    instruction, the name a device trace's op events carry."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    bf = jnp.bfloat16
    if kernel == "fusemax_decode_paged":
        args = [s((B, H, 1, E), bf), s((P, H, PS, E), bf),
                s((P, H, PS, E), bf), s((B, W), jnp.int32),
                s((B,), jnp.int32)]
        fn = lambda q, k, v, bt, n: ops.fusemax_decode_paged(
            q, k, v, bt, n, impl="pallas", interpret=False)
    elif kernel == "fusemax_mla_decode_paged":
        args = [s((B, HM, 1, R + RD), bf), s((P, PS, R), bf),
                s((P, PS, RD), bf), s((B, W), jnp.int32),
                s((B,), jnp.int32)]
        fn = lambda q, c, kr, bt, n: ops.fusemax_mla_decode_paged(
            q, c, kr, bt, n, impl="pallas", interpret=False)
    else:
        args = [s((1, H, T, E), bf)] * 3
        fn = lambda q, k, v: ops.fusemax_attention(
            q, k, v, causal=True, impl="pallas", interpret=False)
    text = _compile(fn, *args).as_text()
    named = [ln for ln in text.splitlines()
             if ln.lstrip().removeprefix("ROOT ").startswith(f"%{kernel}.")]
    assert named and all("tpu_custom_call" in ln for ln in named)
