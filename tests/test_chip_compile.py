"""Compile the main path for a described TPU v5e chip — no chip needed.

The TPU compiler refuses what interpret mode accepts: blocks off the (8, 128)
tiling, ops Mosaic cannot lower, programs that overflow HBM.  These tests
compile the Pallas kernels (``interpret=False``) at the widths of the models
that use them, and the full-width qwen3-1.7b decode step and the engine's
prefill of qwen3-1.7b and mamba2-2.7b, and the mamba2-2.7b decode step, for a
chip that is described and not attached: they must fit, and must write
their donated caches in place.
Nothing runs, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and under several test workers only
the worker given this file may.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.memory.accountant import pytree_nbytes
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import registry
from repro.models.ssm import ssm_dims
from repro.serving.engine import EngineConfig, ServeEngine

HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("window", [None, 512])
def test_flash_attention_compiles_at_qwen3_widths(one_chip, window):
    cfg = get_config("qwen3-1.7b")
    hd, s = cfg.resolved_head_dim, 2048
    q = _sds((1, cfg.n_heads, s, hd), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.n_kv_heads, s, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    _, h, p, n = ssm_dims(cfg)
    b, s = 1, 2048
    f32 = jnp.float32
    compiled = jax.jit(lambda *a: ssd_scan(
        *a, chunk=cfg.ssm_chunk, interpret=False)).lower(
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), f32, one_chip), _sds((h,), f32, one_chip),
        _sds((b, s, n), f32, one_chip), _sds((b, s, n), f32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_decode_step_fits_one_chip(one_chip):
    cfg = get_config("qwen3-1.7b")
    batch, context = 8, 2048

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(registry.abstract_params(cfg)[0])
    caches = on_chip(jax.eval_shape(
        lambda: registry.init_caches(cfg, batch, context)))
    compiled = jax.jit(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c)).lower(
        params, _sds((batch, 1), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), caches).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_qwen3_decode_step_writes_cache_in_place(one_chip):
    """At decode-long's shape (64 rows, context 512) the compiled step
    holds no cache-sized temporary and copies no cache stack: the donated
    caches take the new position in place."""
    cfg = get_config("qwen3-1.7b")
    batch, context = 64, 512

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(registry.abstract_params(cfg)[0])
    caches = on_chip(jax.eval_shape(
        lambda: registry.init_caches(cfg, batch, context)))
    compiled = jax.jit(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c),
        donate_argnums=(3,)).lower(
        params, _sds((batch, 1), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), caches).compile()
    layer_bytes = caches["k"].size // cfg.n_layers * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    stack = "bf16[{},{},{},{},{}]".format(*caches["k"].shape)
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+)", compiled.as_text(),
                     re.M)
    copies = [name for name, typ in ops
              if name.startswith("copy") and typ.startswith(stack)]
    assert not copies, copies


def test_mamba2_decode_step_writes_state_in_place(one_chip):
    """At the chat cell's shape (16 rows, context 256) the compiled step
    holds no temporary as large as one layer's state and copies neither
    SSM stack: the layer scan writes each layer's conv window and state
    into the donated stacks in place."""
    cfg = get_config("mamba2-2.7b")
    batch, context = 16, 256

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(registry.abstract_params(cfg)[0])
    caches = on_chip(jax.eval_shape(
        lambda: registry.init_caches(cfg, batch, context)))
    compiled = jax.jit(
        lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c),
        donate_argnums=(3,)).lower(
        params, _sds((batch, 1), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), caches).compile()
    state = caches["ssm"]["state"]
    layer_bytes = state.size // cfg.n_layers * 4
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    stacks = ["f32[{}]".format(",".join(map(str, a.shape)))
              for a in (state, caches["ssm"]["conv"])]
    ops = re.findall(r"^\s*(?:ROOT )?%(\S+) = (\S+)", compiled.as_text(),
                     re.M)
    copies = [name for name, typ in ops if name.startswith("copy")
              and typ.split("{")[0] in stacks]
    assert not copies, copies


@pytest.mark.parametrize("arch, rows, prompt, context", [
    ("qwen3-1.7b", 32, 224, 512), ("qwen3-1.7b", 64, 112, 512),
    ("mamba2-2.7b", 16, 112, 256), ("qwen3-1.7b", 8, 4000, 4096)])
def test_prefill_fits_one_chip_and_fills_the_donated_caches(
        one_chip, arch, rows, prompt, context):
    """The engine's prefill program at the cells' longest prompts, and at
    8 prompts of 4000 positions in a 4096-position cache: it fits one chip,
    and the donated caches take the caches it returns in place, for the
    SSM too, whose prefill writes its caches without reading them."""
    cfg = get_config(arch)

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(registry.abstract_params(cfg)[0])
    caches = on_chip(jax.eval_shape(
        lambda: registry.init_caches(cfg, rows, context)))
    engine = ServeEngine(cfg, params, EngineConfig(max_batch=rows,
                                                   max_context=context))
    length = registry.prefill_length(cfg, prompt)
    mem = engine._prefill.lower(
        params, _sds((rows, length), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), caches).compile().memory_analysis()
    assert mem.alias_size_in_bytes == pytree_nbytes(caches)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
