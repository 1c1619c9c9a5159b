"""One prefill call fills the decode caches where the model writes their
layout, and every other layout keeps the prompt replayed.

``ServeEngine.run`` takes the padded prompt batch in one
``registry.prefill_caches`` call for the layouts ``registry.prefill_length``
accepts, and otherwise calls ``registry.decode_step`` once per prompt
position.  These tests hold both paths to a replay that each test runs
itself through ``registry.decode_step``: the same served tokens, and the
last prompt position's logits and the caches within bfloat16 rounding.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.launch.mesh import host_pod_backend
from repro.launch.serve import serve_with_early_restart
from repro.models import registry
from repro.serving import engine as engine_lib
from repro.serving.engine import EngineConfig, Request, ServeEngine

#: the archs whose cache layout the prefill writes: a dense decoder whose
#: every layer attends globally through a ``{"k", "v"}`` cache, and the SSM
PREFILLED = {"qwen3-0.6b", "qwen3-1.7b", "gemma-2b", "mamba2-2.7b"}
#: rows, prompt positions, new tokens, context
B, S, K, C = 3, 7, 6, 32
#: bfloat16 keeps 8 significant bits: two units in its last place at the
#: largest magnitude of a tensor
BF16_TOL = 2.0 ** -6


def _prompts(cfg, seed=0):
    vocab = cfg.vocab_size or cfg.vocab
    return np.random.default_rng(seed).integers(0, vocab, (B, S)
                                                ).astype(np.int32)


def _replay(params, cfg, prompts, new, context=C):
    """The prompt through the decode step one position at a time, then
    ``new`` greedy steps: (the last prompt position's logits, the caches
    the prompt left, the generated tokens [B, new])."""
    step = jax.jit(lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))
    caches = registry.init_caches(cfg, B, context)
    if cfg.family == "audio":
        frames = jnp.zeros((B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        caches = registry.prefill_encoder(params, cfg, {"frames": frames},
                                          caches)
    for pos in range(S):
        logits, caches = step(params, jnp.asarray(prompts[:, pos:pos + 1]),
                              jnp.int32(pos), caches)
    prompt_logits, prompt_caches = logits, jax.tree.map(jnp.copy, caches)
    tok, out = jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None], []
    for k in range(new):
        logits, caches = step(params, tok.astype(jnp.int32),
                              jnp.int32(S + k), caches)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab], -1)[:, None]
        out.append(np.asarray(tok[:, 0]))
    return prompt_logits, prompt_caches, np.reshape(out, (new, B)).T


def _close(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=BF16_TOL * np.abs(want).max())


def _prefill(params, cfg, prompts, context):
    """``registry.prefill_caches`` over ``prompts`` padded as the engine
    pads them: (logits, caches)."""
    caches = registry.init_caches(cfg, B, context)
    n = registry.prefill_length(cfg, S)
    tokens = np.zeros((B, n), np.int32)
    tokens[:, :S] = prompts
    return jax.jit(lambda p, t, i, c: registry.prefill_caches(
        p, cfg, t, i, c))(params, jnp.asarray(tokens), jnp.int32(S), caches)


@pytest.mark.parametrize("arch, context", [
    ("qwen3-1.7b", C), ("qwen3-1.7b", 160), ("mamba2-2.7b", C)])
def test_prefill_leaves_what_the_replay_leaves(arch, context):
    """Logits at the last prompt position and every cache the prompt fills,
    within bfloat16 rounding; a KV cache's positions past the prompt stay
    zero, whether its prefill's pads run past the cache (context 32) or
    lie inside it (context 160)."""
    cfg = get_smoke_config(arch)
    params, _ = registry.init_params(jax.random.PRNGKey(1), cfg)
    prompts = _prompts(cfg, 1)
    logits, got = _prefill(params, cfg, prompts, context)
    want_logits, want, _ = _replay(params, cfg, prompts, 0, context)
    assert logits.shape == want_logits.shape == (B, 1, cfg.vocab)
    vocab = cfg.vocab_size or cfg.vocab
    _close(logits[..., :vocab], want_logits[..., :vocab])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
        if arch == "qwen3-1.7b":        # [L, B, C, KH, hd]
            assert not np.asarray(g[:, :, S:]).any()
            g, w = g[:, :, :S], w[:, :, :S]
        _close(g, w)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_engine_serves_the_replays_tokens(arch, monkeypatch):
    """Every arch at the smoke size: the layouts the prefill writes take
    the prompt in one call, every other layout one call per position (the
    ``calls`` of ``repro.engine.replay``), and both serve the tokens of the
    test's own replay."""
    cfg = get_smoke_config(arch)
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    replays, span = [], engine_lib.span

    def recorded(name, **args):
        if name == "engine.replay":
            replays.append(args)
        return span(name, **args)
    monkeypatch.setattr(engine_lib, "span", recorded)
    engine = ServeEngine(cfg, params, EngineConfig(max_batch=B,
                                                   max_context=C))
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=K)
            for i in range(B)]
    engine.run(reqs)
    prefilled = arch in PREFILLED
    assert (registry.prefill_length(cfg, S) is not None) == prefilled
    assert replays == [{"positions": S, "calls": 1 if prefilled else S}]
    want_logits, _, want = _replay(params, cfg, prompts, K)
    assert [r.generated for r in reqs] == want.tolist()
    vocab = cfg.vocab_size or cfg.vocab
    _close(engine.prompt_logits[..., :vocab], want_logits[..., :vocab])


def test_restart_comes_at_the_same_decode_step(monkeypatch):
    """A batch started on a slice too small for it, as in the chat-restart
    cell: the predictor sees the same series whether the prompt took one
    call or one per position, so the first attempt is thrown away after
    the same decode step, and both serve the same tokens."""
    cfg = get_smoke_config("qwen3-1.7b")
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    backend = host_pod_backend(["d0"])

    def serve():
        rng = np.random.default_rng(0)
        reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 16)
                        .astype(np.int32), max_new_tokens=12)
                for i in range(4)]
        at = []
        res = serve_with_early_restart(
            cfg, params, reqs, backend=backend, max_context=64,
            partition_gb=1e-4,
            log=lambda m: at.append(len(reqs[0].generated)))
        return at, [r.generated for r in res.requests]
    at, served = serve()
    monkeypatch.setattr(registry, "prefill_length", lambda cfg, s: None)
    assert serve() == (at, served)
    assert len(at) == 1 and 0 < at[0] < 12


@pytest.mark.parametrize("arch, prompt, length", [
    ("qwen3-1.7b", 80, 128), ("qwen3-1.7b", 112, 128),
    ("qwen3-1.7b", 160, 256), ("qwen3-1.7b", 224, 256),
    ("qwen3-1.7b", 512, 512), ("qwen3-1.7b", 513, 1024),
    ("qwen3-1.7b", 4000, 4096), ("mamba2-2.7b", 80, 80),
    ("mamba2-2.7b", 112, 112)])
def test_prefill_length(arch, prompt, length):
    """A KV prompt is padded to a multiple of 128 positions, or of
    ``attn_q_block`` (512) past it, so that a few programs serve every
    prompt length and a long prompt's attention scans query blocks; an
    SSM's prompt is taken as it is, since a pad would enter its state."""
    assert registry.prefill_length(get_config(arch), prompt) == length


def test_prompts_of_one_bucket_share_a_prefill_program():
    """Batches whose longest prompts differ but pad to the same length run
    one prefill program: the second batch compiles nothing."""
    cfg = get_smoke_config("qwen3-1.7b")
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, EngineConfig(max_batch=B,
                                                   max_context=C))
    programs = []
    for n in (S, S - 2):
        engine.run([Request(uid=i, prompt=p[:n], max_new_tokens=2)
                    for i, p in enumerate(_prompts(cfg))])
        programs.append(engine._prefill._cache_size())
    assert programs == [1, 1]


#: sha256 of the engine's decode step for the cells' shapes, lowered
#: (``Lowered.as_text()``, JAX 0.9.0): qwen3's before the prefill was
#: added, which left the decode program unchanged; mamba2's since its layer
#: scan carries the SSM stacks and writes each layer's state in place
#: (``ssm.ssm_decode_layer``) instead of returning them restacked
DECODE_STEP_SHA256 = {
    ("qwen3-1.7b", 32, 512):
        "28c2f10cc932101902f51318279569f8557f43e8d5b74341cc3348b197578c56",
    ("qwen3-1.7b", 64, 512):
        "9149519e5be7373adc08cc4c5015990657f9f5302dae70a06092a9871360add5",
    ("mamba2-2.7b", 16, 256):
        "d4ff425a32fe4df108f51b00c4724202fd487732255ee4f81331137127cf20d8",
}


@pytest.mark.parametrize("arch, rows, context", list(DECODE_STEP_SHA256))
def test_decode_step_lowers_as_before(arch, rows, context):
    cfg = get_config(arch)

    def decode_step(p, t, i, c):        # named as the engine names it
        return registry.decode_step(p, cfg, t, i, c)
    caches = jax.eval_shape(lambda: registry.init_caches(cfg, rows, context))
    text = jax.jit(decode_step, donate_argnums=(3,)).lower(
        registry.abstract_params(cfg)[0],
        jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), caches).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == DECODE_STEP_SHA256[arch, rows, context]


#: sha256 of the engine's prefill for the cells' rows, padded prompt
#: lengths and contexts, lowered (``Lowered.as_text()``, JAX 0.9.0) before
#: the int8 and ring-buffer cache layouts were deleted: the prefill
#: programs the cells run do not change with it
PREFILL_SHA256 = {
    ("qwen3-1.7b", 32, 256, 512):
        "4a961809484c7ad2262c731e1da7a696d74b39d4f24c7f8fcbf40c0458be1d77",
    ("qwen3-1.7b", 64, 128, 512):
        "3069f031dcb0c12fe37569657b2b9ceff37693fa1cedd2dec0f57e5174ac0eb1",
    ("mamba2-2.7b", 16, 80, 256):
        "f5181c9f166f6a1711fbf2e5828f199574abaae51388110231618d9072a0b089",
    ("mamba2-2.7b", 16, 112, 256):
        "602e3ce51dbd93a1a660601c2ffec82e03f44602c63cd1c386542aa77e75f366",
}


@pytest.mark.parametrize("arch, rows, length, context", list(PREFILL_SHA256))
def test_prefill_lowers_as_before(arch, rows, length, context):
    cfg = get_config(arch)

    def prefill(p, t, n, c):            # named as the engine names it
        return registry.prefill_caches(p, cfg, t, n, c)
    caches = jax.eval_shape(lambda: registry.init_caches(cfg, rows, context))
    text = jax.jit(prefill, donate_argnums=(3,), keep_unused=True).lower(
        registry.abstract_params(cfg)[0],
        jax.ShapeDtypeStruct((rows, length), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32), caches).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == PREFILL_SHA256[arch, rows, length, context]


def test_ragged_prompts_keep_their_trailing_pads():
    """A batch's shorter prompts are padded at their end with id 0 and the
    pads go into the caches, in the prefill as in the replay."""
    cfg = get_smoke_config("qwen3-1.7b")
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    padded = prompts.copy()
    padded[0, 4:] = 0
    reqs = [Request(uid=i, prompt=prompts[i][:4] if i == 0 else prompts[i],
                    max_new_tokens=K) for i in range(B)]
    ServeEngine(cfg, params,
                EngineConfig(max_batch=B, max_context=C)).run(reqs)
    assert [r.generated for r in reqs] == \
        _replay(params, cfg, padded, K)[2].tolist()
