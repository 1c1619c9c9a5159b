"""Batched serving engine with allocator instrumentation.

This is where the paper's dynamic-memory machinery meets real JAX execution:
the engine runs prefill + decode for a batch of requests, the
:class:`MemoryAccountant` records per-iteration requested/live bytes (params,
KV cache growth, activation churn), and the :class:`PeakMemoryPredictor`
watches the series.  When the converged prediction exceeds the partition the
engine raises :class:`NeedsLargerPartition` — the early restart — and the
multi-tenant launcher migrates the job to a bigger sub-slice.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.memory.accountant import MemoryAccountant, pytree_nbytes
from repro.core.memory.timeseries import PeakMemoryPredictor
from repro.core.restart import NeedsLargerPartition, early_restart_target
from repro.core.partition_state import PartitionBackend, PartitionProfile
from repro.models import registry
from repro.obs.spans import compile_log, span

GB = 1024 ** 3
_LOADED = contextlib.nullcontext()


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    #: ``time.perf_counter()`` at which each token of ``generated`` reached
    #: the host: one stamp per decode step, shared by the batch's rows
    token_times: list[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_context: int = 512
    #: slice the engine believes it has; set, the time-series predictor
    #: raises the early restart when the batch will not fit it (paper)
    partition_gb: float | None = None
    #: SLO-aware restart trade (mirrors the simulator's grow trade,
    #: cost.serving_grow_cost): when both are set, the engine restarts as
    #: soon as the predictor's graded OOM risk prices the expected crash
    #: (``risk * crash_cost_s``) above one restart (``restart_cost_s``) —
    #: instead of waiting for the converged point estimate to cross the
    #: partition.  Left at 0.0, the paper's binary trigger is unchanged.
    crash_cost_s: float = 0.0
    restart_cost_s: float = 0.0


class ServeEngine:
    """Greedy batched decode over a fixed request batch."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 engine_cfg: EngineConfig,
                 backend: PartitionBackend | None = None) -> None:
        self.cfg = cfg
        self.params = params
        self.ecfg = engine_cfg
        self.backend = backend
        self._reset_run_state()
        self._params_bytes = pytree_nbytes(params)
        compile_log()       # listening before this engine's step compiles

        def decode_step(p, t, i, c):
            return registry.decode_step(p, cfg, t, i, c)

        def prefill(p, t, n, c):
            return registry.prefill_caches(p, cfg, t, n, c)
        # the caches are donated: each step writes them in place.  Without
        # it every step queued ahead of the device holds a fresh copy of
        # the whole cache, and the queue fills the chip's HBM.
        self._decode = jax.jit(decode_step, donate_argnums=(3,))
        # an SSM's prefill writes its caches without reading them: kept as
        # arguments, the donated buffers take the new caches in place
        self._prefill = jax.jit(prefill, donate_argnums=(3,),
                                keep_unused=True)
        #: the programs this engine has called once: ``"decode"``,
        #: ``"prefill"``
        self._loaded: set[str] = set()

    def _reset_run_state(self) -> None:
        """Fresh per-run accounting: a second batch on the same engine must
        not inherit the previous run's live watermark (it would record a
        bogus first-iteration allocation) nor its converged predictor."""
        self.accountant = MemoryAccountant()
        self.predictor = PeakMemoryPredictor(max_iter=self.ecfg.max_context)
        self._last_live = 0.0

    # -- serving loop ------------------------------------------------------------

    def run(self, requests: list[Request]) -> list[Request]:
        cfg, ecfg = self.cfg, self.ecfg
        assert len(requests) <= ecfg.max_batch
        self._reset_run_state()
        b = len(requests)
        prompt_len = max(len(r.prompt) for r in requests)
        with span("engine.setup") as mark:
            caches = registry.init_caches(cfg, b, ecfg.max_context)
            mark.set_metadata(
                cache_kind="ssm" if cfg.is_attention_free else "kv",
                cache_bytes=pytree_nbytes(caches))
            # the padded prompt batch, and the pads after it that the
            # prefill program for this layout takes
            length = registry.prefill_length(cfg, prompt_len)
            toks = np.zeros((b, length or prompt_len), np.int32)
            for i, r in enumerate(requests):
                toks[i, :len(r.prompt)] = r.prompt
            batch = {"tokens": jnp.asarray(toks)}
            if cfg.family == "audio":
                batch["frames"] = jnp.zeros((b, cfg.enc_seq, cfg.d_model),
                                            jnp.bfloat16)
                caches = registry.prefill_encoder(self.params, cfg, batch,
                                                  caches)
        # fill the caches: one prefill call where the model writes this
        # cache layout, else the prompt replayed through decode_step, one
        # call per position.  The programs are dispatched here and in the
        # decode loop, not through a helper method: called from one, the
        # step's first call lowered 3.5 times slower on a TPU v5e host
        # (0.31-0.36 s against 0.09 s), every engine
        with span("engine.replay", positions=prompt_len,
                  calls=prompt_len if length is None else 1):
            if length is None:
                for pos in range(prompt_len):
                    with self._load_span("decode"):
                        logits, caches = self._decode(
                            self.params, batch["tokens"][:, pos:pos + 1],
                            jnp.int32(pos), caches)
            else:
                with self._load_span("prefill"):
                    logits, caches = self._prefill(
                        self.params, batch["tokens"], jnp.int32(prompt_len),
                        caches)
            #: logits at the last prompt position, kept for reference checks
            self.prompt_logits = logits
            next_tok = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1)[:, None]
            if "decode" not in self._loaded:
                # after a prefill the decode step compiles (or loads) here,
                # while the device runs the prefill, and its first call in
                # the decode loop finds it ready
                with self._load_span("decode"):
                    self._decode.lower(self.params,
                                       next_tok.astype(jnp.int32),
                                       jnp.int32(prompt_len), caches
                                       ).compile()
            self._note_iteration(caches, prompt_len)

        steps = min(max(r.max_new_tokens for r in requests),
                    ecfg.max_context - prompt_len)
        with span("engine.decode", steps=steps):
            for step in range(steps):
                pos = prompt_len + step
                logits, caches = self._decode(self.params,
                                              next_tok.astype(jnp.int32),
                                              jnp.int32(pos), caches)
                next_tok = jnp.argmax(logits[:, -1, :cfg.vocab],
                                      axis=-1)[:, None]
                row = next_tok[:, 0]
                with span("engine.sync"):
                    toks_np = np.asarray(row)
                now = time.perf_counter()
                for i, r in enumerate(requests):
                    if not r.done:
                        r.generated.append(int(toks_np[i]))
                        r.token_times.append(now)
                self._check_memory(caches, pos)
        return requests

    def _load_span(self, program: str):
        """``repro.engine.load`` for this engine's first call of
        ``program``, which traces, lowers and compiles (or loads) it; a
        no-op for the calls after."""
        if program in self._loaded:
            return _LOADED
        self._loaded.add(program)
        return span("engine.load", program=program)

    # -- instrumentation (paper §3.2.2) --------------------------------------------

    def _live_bytes(self, caches, upto: int) -> float:
        """Live = params + the *used* prefix of the KV cache + activations.

        The cache tensor is preallocated at max_context; physically-used
        bytes grow with the context — exactly the growth the paper's
        predictor is designed to catch.
        """
        cache_total = pytree_nbytes(caches)
        frac = min(1.0, upto / self.ecfg.max_context)
        if self.cfg.family == "ssm":
            frac = 1.0  # constant-size recurrent state
        act = self._params_bytes * 0.002 + 4 * self.cfg.d_model * 1024
        return self._params_bytes + cache_total * frac + act

    def _note_iteration(self, caches, upto: int) -> None:
        live = self._live_bytes(caches, upto)
        churn = 2 * self.cfg.d_model * max(self.cfg.d_ff, self.cfg.d_model) \
            * 2e-3 + live * 0.01
        self.accountant.note_alloc(churn + max(0.0, live - self._last_live))
        self.accountant.note_live(live)
        self._last_live = live
        self.accountant.end_iteration()

    def _restart_now(self, partition_bytes: float, pred) -> bool:
        """The early-restart decision: the graded SLO trade when priced
        (expected crash seconds vs one restart), else the paper's binary
        converged-prediction threshold."""
        if self.ecfg.crash_cost_s > 0.0 and self.ecfg.restart_cost_s > 0.0:
            if not pred.converged:
                return False
            risk = self.predictor.oom_risk(partition_bytes, pred)
            return risk * self.ecfg.crash_cost_s > self.ecfg.restart_cost_s
        return self.predictor.will_oom(partition_bytes, pred)

    def _check_memory(self, caches, upto: int) -> None:
        with span("engine.predictor"):
            self._note_iteration(caches, upto)
            if not self.ecfg.partition_gb:
                return
            stats = self.accountant.history[-1]
            pred = self.predictor.observe(stats.requested_bytes,
                                          stats.reuse_ratio)
            if self._restart_now(self.ecfg.partition_gb * GB, pred):
                target = None
                if self.backend is not None:
                    target = early_restart_target(self.backend,
                                                  pred.peak_mem_bytes / GB)
                raise NeedsLargerPartition(
                    target or _synthetic_profile(pred.peak_mem_bytes / GB))


def _synthetic_profile(mem_gb: float) -> PartitionProfile:
    return PartitionProfile(name=f"needs-{mem_gb:.1f}gb", mem_gb=mem_gb,
                            compute_fraction=0.0)
