"""Mamba-2 at its published settings: a float32 residual stream, RMSNorm
at 1e-5 and logits only over the published vocabulary, checked against the
benchmark's plain float32 reference (``chipbench/ref/mamba2.py``, loaded
from its file) at the smoke width on the CPU."""

import dataclasses
import glob
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.configs.base import reduce_for_smoke
from repro.core.memory.accountant import pytree_nbytes
from repro.launch.mesh import host_pod_backend
from repro.launch.serve import serve_with_early_restart
from repro.models import registry
from repro.serving.engine import EngineConfig, Request, ServeEngine

CHIPBENCH = Path(__file__).resolve().parents[1] / "chipbench"
ARCH = "mamba2-2.7b"
#: prompt positions, decode steps, rows
T, K, B = 16, 8, 8
#: RMS error of the served logits against the float32 reference, as a share
#: of the reference's RMS, at 64 layers.  The program runs its mixer in
#: bfloat16 (inputs, projections, gate and gated norm): with the residual in
#: float32 that alone reads 3.79-4.14% over seeds 1-6; the residual rounded
#: to bfloat16 after each of the 64 layers adds about 0.7 points, 4.65-4.79%
#: over the same seeds.  The limit lies between the two.
RMS_LIMIT = 0.044


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """The benchmark's mamba2 reference, its harness (``run.py``) and its
    smoke sizing (``tests/smoke.py``), each read from its own file."""
    added = [str(CHIPBENCH), str(CHIPBENCH / "tests")]
    sys.path[:0] = added
    try:
        harness = _load(CHIPBENCH / "run.py", "run")
        ns = dict(ref=_load(CHIPBENCH / "ref" / "mamba2.py", "ref.mamba2"),
                  harness=harness,
                  smoke=_load(CHIPBENCH / "tests" / "smoke.py", "smoke"))
    finally:
        for p in added:
            sys.path.remove(p)
    config = json.loads((CHIPBENCH / "configs" / f"{ARCH}.json").read_text())
    return dataclasses.make_dataclass("Bench", ["ref", "harness", "smoke",
                                                "config"])(config=config, **ns)


def _setup(bench, n_layers):
    """The smoke program with ``n_layers`` layers, the reference's
    hyperparameters to match, and the table's rows."""
    hp = bench.smoke.smoke_config(bench.config)
    hp["n_layer"] = n_layers
    cfg = dataclasses.replace(get_smoke_config(ARCH), n_layers=n_layers)
    rows = registry.abstract_params(cfg)[0]["embedding"].shape[0]
    return hp, cfg, rows


def _decode(cfg, weights, prompts):
    """Replays ``prompts`` [B, T] through the decode step, then decodes
    greedily; returns the sequence fed in and the logits over the published
    vocabulary at each position from the last prompt one on."""
    step = jax.jit(lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))
    caches = registry.init_caches(cfg, B, T + K)
    seq, out = [prompts[:, i] for i in range(T)], []
    for pos in range(T + K - 1):
        logits, caches = step(weights, jnp.asarray(seq[pos])[:, None],
                              jnp.int32(pos), caches)
        if pos >= T - 1:
            out.append(np.asarray(logits[:, 0, :cfg.vocab_size], np.float32))
            seq.append(np.asarray(jnp.argmax(logits[:, 0, :cfg.vocab], -1),
                                  np.int32))
    return np.stack(seq[:T + K - 1], 1), np.stack(out, 1)


@pytest.mark.parametrize("seed", [1, 2])
def test_served_logits_follow_the_float32_reference(bench, seed):
    """Bfloat16 weights from the reference's own initialisation, the prompt
    replayed and a few tokens decoded through the serving step, at the
    published 64 layers: within ``RMS_LIMIT`` of the reference, which the
    same program with a bfloat16 residual exceeds."""
    hp, cfg, rows = _setup(bench, 64)
    assert cfg.residual_in_fp32 and cfg.norm_eps == hp["norm_epsilon"]
    w = jax.jit(lambda k: bench.ref.init_weights(k, hp, rows))(
        jax.random.PRNGKey(seed))
    prompts = np.random.default_rng(seed).integers(
        0, hp["vocab_size"], (B, T)).astype(np.int32)
    positions = jnp.broadcast_to(jnp.arange(T - 1, T + K - 1), (B, K))
    reference = bench.ref.make_logits_at(hp)

    def rms_error(cfg):
        seq, got = _decode(cfg, w, prompts)
        want = np.asarray(reference(w, jnp.asarray(seq), positions))
        return float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean()))
    assert rms_error(cfg) < RMS_LIMIT
    assert rms_error(dataclasses.replace(cfg, residual_in_fp32=False)) \
        > RMS_LIMIT


@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_serves_what_the_replay_serves(bench, seed):
    """At the published 64 layers the engine's one prefill call leaves the
    last prompt position's logits and the caches within the program's own
    bfloat16 error of the test's own replay: nearer the replay's than the
    replay's logits lie to the float32 reference, and its logits under
    ``RMS_LIMIT`` of that reference, as the replay's are.  It serves the
    replay's first tokens, and the replay's generated tokens up to where
    the replay's own logits nearly tie: a row may part from the replay only
    at a token whose logit the replay puts less than the two paths' widest
    logit difference below its best."""
    hp, cfg, rows = _setup(bench, 64)
    w = jax.jit(lambda k: bench.ref.init_weights(k, hp, rows))(
        jax.random.PRNGKey(seed))
    prompts = np.random.default_rng(seed).integers(
        0, hp["vocab_size"], (B, T)).astype(np.int32)
    seq, replayed = _decode(cfg, w, prompts)
    engine = ServeEngine(cfg, w, EngineConfig(max_batch=B,
                                              max_context=T + K))
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=K - 2)
            for i in range(B)]
    engine.run(reqs)
    logits = np.asarray(engine.prompt_logits[:, 0, :cfg.vocab_size],
                        np.float32)

    def rms(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.sqrt(((got - want) ** 2).mean()
                             / (want ** 2).mean()))
    want = bench.ref.make_logits_at(hp)(
        w, jnp.asarray(prompts), jnp.full((B, 1), T - 1))[:, 0]
    own = rms(replayed[:, 0], want)
    assert rms(logits, replayed[:, 0]) < own
    assert rms(logits, want) < RMS_LIMIT
    # the replay's tokens: its first token at T, then those it fed on
    assert (logits.argmax(-1) == seq[:, T]).all()
    rounding = np.abs(logits - replayed[:, 0]).max()
    for row, r in enumerate(reqs):
        parted = [j for j, tok in enumerate(r.generated)
                  if tok != seq[row, T + 1 + j]]
        if parted:
            at = replayed[row, parted[0] + 1]
            assert at.max() - at[r.generated[parted[0]]] <= rounding
    step = jax.jit(lambda p, t, i, c: registry.decode_step(p, cfg, t, i, c))
    caches = registry.init_caches(cfg, B, T + K)
    for pos in range(T):
        _, caches = step(w, jnp.asarray(prompts[:, pos:pos + 1]),
                         jnp.int32(pos), caches)
    _, got = jax.jit(lambda p, t, c: registry.prefill_caches(
        p, cfg, t, T, c))(w, jnp.asarray(prompts),
                          registry.init_caches(cfg, B, T + K))
    for name in ("conv", "state"):
        assert rms(got["ssm"][name], caches["ssm"][name]) < own


@pytest.mark.parametrize("path", ["forward", "decode_step"])
def test_float32_program_is_the_reference(bench, path):
    """On float32 weights the residual flag changes nothing, and both the
    program's forward pass and its decode step compute the reference's map
    at the published epsilon: only the order of sums differs."""
    hp, cfg, rows = _setup(bench, 2)
    w = jax.jit(lambda k: bench.ref.init_weights(k, hp, rows))(
        jax.random.PRNGKey(3))
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    prompts = np.random.default_rng(3).integers(
        0, hp["vocab_size"], (B, T)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        if path == "forward":
            got = registry.forward(w32, cfg, {"tokens": jnp.asarray(prompts)}
                                   ).logits[:, T - 1:, :cfg.vocab_size]
            seq = prompts
        else:
            seq, got = _decode(cfg, w32, prompts)
    k = got.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T - 1, T - 1 + k), (B, k))
    want = bench.ref.make_logits_at(hp)(w, jnp.asarray(seq), positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("size", ["full", "smoke"])
def test_harness_accepts_the_published_settings(bench, size):
    config = bench.config
    if size == "smoke":
        config = bench.smoke.smoke_config(config)
    cfg = bench.harness.program_config(config, smoke=size == "smoke")
    assert (cfg.norm_eps, cfg.residual_in_fp32) == (1e-5, True)
    if size == "full":
        assert (cfg.vocab, cfg.vocab_size) == (50280, 50277)
        assert cfg.vocab_size == bench.config["vocab_size"]


@pytest.mark.parametrize("masked", [True, False])
def test_served_ids_stay_in_the_published_vocabulary(masked):
    """Padding rows of the table scaled up until their logits dominate: the
    engine serves none of them, the first token included.  Without the
    published vocabulary (``vocab_size`` None) the same weights serve them
    at most positions (a row wins where its logit is positive), so the
    planted rows do win where nothing masks them."""
    cfg = get_smoke_config(ARCH)
    if not masked:
        cfg = dataclasses.replace(cfg, vocab_size=None)
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    vs = get_smoke_config(ARCH).vocab_size
    table = params["embedding"]
    params["embedding"] = table.at[vs:].set(table[vs:] * 1000)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, vs, 6).astype(np.int32),
                    max_new_tokens=5) for i in range(3)]
    res = serve_with_early_restart(cfg, params, reqs,
                                   backend=host_pod_backend(["d0"]),
                                   max_context=16)
    first = np.asarray(jnp.argmax(
        res.engine.prompt_logits[:, -1, :cfg.vocab], -1))
    served = np.concatenate([first] + [r.generated for r in res.requests])
    assert served.size == 3 + 15
    if masked:
        assert served.max() < vs
    else:
        assert (served >= vs).sum() > served.size // 2


def test_qwen3_decode_step_unchanged_by_its_vocab_size():
    """Where the published vocabulary is the whole table, the decode step
    lowers to the same program as with no ``vocab_size``."""
    cfg = get_config("qwen3-1.7b")
    shapes = registry.abstract_params(cfg)[0]
    caches = jax.eval_shape(lambda: registry.init_caches(cfg, 4, 64))

    def lowered(cfg):
        return jax.jit(lambda p, t, i, c: registry.decode_step(
            p, cfg, t, i, c)).lower(
                shapes, jax.ShapeDtypeStruct((4, 1), jnp.int32),
                jnp.int32(0), caches).as_text()
    assert lowered(cfg) == lowered(dataclasses.replace(cfg,
                                                       vocab_size=cfg.vocab))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_reduction_keeps_the_vocabulary_padding(arch):
    full, cfg = get_config(arch), get_smoke_config(arch)
    padding = full.vocab - (full.vocab_size or full.vocab)
    assert cfg.vocab - (cfg.vocab_size or cfg.vocab) == padding
    assert reduce_for_smoke(full).vocab_size == cfg.vocab_size
    assert cfg.residual_in_fp32 == full.residual_in_fp32


@pytest.mark.parametrize("arch, kind", [(ARCH, "ssm"), ("qwen3-1.7b", "kv")])
def test_setup_span_names_the_cache(arch, kind, tmp_path):
    """``repro.engine.setup`` carries the kind of cache the engine made and
    its bytes on the device."""
    cfg = get_smoke_config(arch)
    params, _ = registry.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, EngineConfig(max_batch=2,
                                                   max_context=16))
    reqs = [Request(uid=i, prompt=np.arange(1, 5, dtype=np.int32),
                    max_new_tokens=2) for i in range(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(reqs)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    setups = [dict(e.stats) for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name == "repro.engine.setup"]
    assert setups == [{"cache_kind": kind, "cache_bytes": pytree_nbytes(
        registry.init_caches(cfg, 2, 16))}]
