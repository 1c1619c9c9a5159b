"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each assigned architecture runs one forward + one train step on CPU with
correct shapes and no NaNs; decode preserves cache shapes."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.models import registry
from repro.models.layers import padded_vocab
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step

BATCH, SEQ = 2, 32


@pytest.fixture(scope="module")
def smoke_state():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_smoke_config(arch)
            params, specs = registry.init_params(jax.random.PRNGKey(0), cfg)
            cache[arch] = (cfg, params, specs)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_matches_assignment(arch):
    """The full (dry-run) configs carry the exact assigned dimensions."""
    cfg = get_config(arch)
    assigned = {
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "gemma-2b": (18, 2048, 8, 1, 16384, 256000),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab)
    assert got == assigned, f"{arch}: {got} != {assigned}"
    assert cfg.source, "every config must cite its source"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_reduction_bounds(arch):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finiteness(arch, smoke_state):
    cfg, params, _ = smoke_state(arch)
    batch = registry.make_dummy_batch(cfg, BATCH, SEQ)
    out = registry.forward(params, cfg, batch)
    assert out.logits.shape == (BATCH, SEQ, padded_vocab(cfg))
    assert not bool(jnp.isnan(out.logits).any())
    assert jnp.isfinite(out.aux_loss)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch, smoke_state):
    cfg, _, _ = smoke_state(arch)
    state, _ = init_train_state(jax.random.PRNGKey(1), cfg)
    step = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1,
                                                    total_steps=10)))
    batch = registry.make_dummy_batch(cfg, BATCH, SEQ)
    state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
    assert metrics["grad_norm"] > 0.0  # gradients actually flow
    # params actually moved
    leaf0 = jax.tree_util.tree_leaves(state["params"])[0]
    assert not bool(jnp.isnan(leaf0).any())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_step_cache_invariants(arch, smoke_state):
    cfg, params, _ = smoke_state(arch)
    caches = registry.init_caches(cfg, BATCH, 64)
    if cfg.family == "audio":
        b = registry.make_dummy_batch(cfg, BATCH, 8)
        caches = registry.prefill_encoder(params, cfg, b, caches)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    logits, caches2 = registry.decode_step(params, cfg, tok, jnp.int32(3),
                                           caches)
    assert logits.shape == (BATCH, 1, padded_vocab(cfg))
    assert not bool(jnp.isnan(logits).any())
    shapes_ok = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
        caches, caches2))
    assert shapes_ok


@pytest.mark.parametrize(
    "arch, context",
    [("qwen3-0.6b", 16), ("gemma3-27b", 16), ("mamba2-2.7b", 16),
     ("zamba2-7b", 16), ("whisper-medium", 16),
     # MoE MLP and the VLM decoder in the plain attention body
     ("grok-1-314b", 16), ("pixtral-12b", 16),
     # interleaved dense / MoE pairs with chunked attention
     ("llama4-maverick-400b-a17b", 16),
     # the last step writes the cache's last slot (index == C - 1)
     ("qwen3-0.6b", 8)],
    ids=["qwen3-0.6b", "gemma3-27b", "mamba2-2.7b", "zamba2-7b",
         "whisper-medium", "grok-1-314b", "pixtral-12b",
         "llama4-maverick-400b-a17b", "qwen3-0.6b-last-slot"])
def test_prefill_decode_consistency(arch, context, smoke_state):
    """Teacher-forced logits == step-by-step decode logits (f32)."""
    import dataclasses

    from repro.models.module import cast_tree
    cfg, params, _ = smoke_state(arch)
    if cfg.n_experts:
        # room for every token in the forward's expert queues: a decode
        # step of one token never drops one
        cfg = dataclasses.replace(
            cfg, capacity_factor=cfg.n_experts / cfg.top_k)
    params32 = cast_tree(params, jnp.float32)
    S = 8
    batch = registry.make_dummy_batch(cfg, BATCH, S,
                                      key=jax.random.PRNGKey(7))
    # decode embeds tokens only, so the VLM's forward takes no patches
    batch.pop("patches", None)
    batch = {k: (v.astype(jnp.float32) if v.dtype == jnp.bfloat16 else v)
             for k, v in batch.items()}
    full = registry.forward(params32, cfg, batch).logits
    caches = registry.init_caches(cfg, BATCH, context)
    caches = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        caches)
    if cfg.family == "audio":
        caches = registry.prefill_encoder(params32, cfg, batch, caches)
    for i in range(S):
        logits, caches = registry.decode_step(
            params32, cfg, batch["tokens"][:, i:i + 1], jnp.int32(i), caches)
        err = jnp.abs(logits[:, 0] - full[:, i]).max()
        scale = jnp.abs(full[:, i]).max() + 1e-9
        assert float(err / scale) < 5e-3, f"{arch} step {i}"
