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
import re

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


STABLELM_SLOTS, STABLELM_MAX_LEN = 8, 1024


@pytest.fixture(scope="module")
def stablelm_loop(one_chip):
    """One fused decode dispatch of stablelm-1.6b in bf16 through the
    Pallas kernels, at ``chip_smoke.py``'s pool (8 slots x 1024 tokens),
    compiled for the described chip."""
    from repro.configs import get_config
    from repro.model import transformer as tf
    from repro.model.layers import Runtime

    cfg = get_config("stablelm-1.6b")
    rt = Runtime(attn_impl="pallas", interpret=False,
                 param_dtype=jnp.bfloat16, activation_dtype=jnp.bfloat16)
    slots, max_len = STABLELM_SLOTS, STABLELM_MAX_LEN
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

    return jax.jit(loop, donate_argnums=(1, 2, 3, 4, 5)).lower(
        params, caches, s((slots,), jnp.int32),
        s((slots, cfg.vocab), jnp.float32), s((slots,), jnp.int32),
        s((2,), jnp.uint32), {"full": s((slots, max_len // PS), jnp.int32)},
    ).compile()


def test_stablelm_decode_loop_fits_one_chip(stablelm_loop):
    """The compiler fits the decode dispatch in one v5e's HBM (it raises
    otherwise)."""
    compiled = stablelm_loop
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes + mem.output_size_in_bytes)
    assert live < 16e9, mem


_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_DIMS = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def _hlo_computations(text: str) -> dict:
    """Computation name → {instruction name: (dims, opcode, operands,
    called computations)} of an HLO module's text; ``dims`` are those of
    the result (of its first element, for a tuple)."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head:
            cur = comps.setdefault(head.group(1), {})
            continue
        m = _INSTR.match(line) if cur is not None else None
        if m is None:
            continue
        name, shape, opcode, rest = m.groups()
        dims = _DIMS.search(shape)
        dims = tuple(int(d) for d in dims.group(1).split(",") if d) \
            if dims else None
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        called = _CALLED.findall(rest)
        called += re.findall(r"%([\w.\-]+)", "".join(
            re.findall(r"branch_computations=\{([^}]*)\}", rest)))
        cur[name] = (dims, opcode, operands, called)
    return comps


def _in_loops(comps: dict) -> set:
    """Every computation a ``while`` body or condition reaches: the
    decode loop, the layer loop inside it, and what they call or fuse."""
    todo = [c for instrs in comps.values()
            for _, op, _, called in instrs.values() if op == "while"
            for c in called]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [d for *_, called in comps[c].values() for d in called]
    return seen


def test_stablelm_decode_loop_updates_pools_in_place(stablelm_loop):
    """Inside the decode and layer loops the stacked K/V pools are only
    updated in place, a few rows per layer, and read by the kernel: no
    whole layer's pool is ever materialised there (sliced out, scattered
    into, or stacked back into a fresh pool), no copy of a whole pool
    runs, and no update writes a whole layer.  The pool's layout copies at
    the program's entry and exit, outside the loops, are allowed.  With
    none of those pool-sized buffers, the temporaries fall to about the
    lane-padded pools themselves."""
    from repro.configs import get_config

    cfg = get_config("stablelm-1.6b")
    n_pages = STABLELM_SLOTS * STABLELM_MAX_LEN // PS
    layer = (n_pages, cfg.n_kv_heads, PS, cfg.dh)
    pool = (cfg.n_layers, *layer)
    whole_layer = {layer, (1, *layer)}

    comps = _hlo_computations(stablelm_loop.as_text())
    loops = _in_loops(comps)
    assert loops
    bad = []
    for c in loops:
        instrs = comps[c]
        for name, (dims, op, operands, _) in instrs.items():
            if dims in whole_layer:
                bad.append((c, name, op, dims))
            elif dims == pool and op in ("copy", "copy-start", "copy-done",
                                         "dynamic-slice"):
                bad.append((c, name, op, dims))
            elif op == "dynamic-update-slice":
                update = instrs.get(operands[1], (None,))[0]
                if update in whole_layer or update == pool:
                    bad.append((c, name, op, update))
    assert not bad, bad
    assert stablelm_loop.memory_analysis().temp_size_in_bytes < 5 * 2**30


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
