"""Driver for serving mixes: closed loops of whole batches through
``repro.launch.serve.serve_with_early_restart`` on one chip.

Each batch is issued when the previous one has returned.  Its requests
start on the mix's ``start_slice_gb``; where that is smaller than the model
needs, the program's predictor restarts the batch on a larger slice, and
the thrown-away attempt counts as time.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import traffic as traffic_lib
from ref.common import widest_gap


def input_scale(cfg) -> float:
    """The factor by which the program multiplies a row of its embedding
    table before the first layer (sqrt(d_model) for its dense family, 1
    where it applies none), read from the program's own embedding."""
    from repro.models.layers import embed_tokens
    one = embed_tokens({"embedding": jnp.ones((1, cfg.d_model), jnp.bfloat16)},
                       jnp.zeros((1, 1), jnp.int32), cfg)
    return float(one[0, 0, 0])


def reparametrize(weights: dict, scale: float) -> dict:
    """The published weights in the program's parametrization.  A program
    that multiplies its input embedding by ``scale`` computes the published
    model exactly when its tied table is the published one divided by
    ``scale`` and, where the head is that table, its final norm's weight the
    published one times ``scale``: the first layer sees the published
    embedding, and the head's logits (final norm times table) are unchanged.
    With ``scale`` 1 the weights are the published ones."""
    if scale == 1.0:
        return weights

    def times(name, factor):
        w = weights[name]
        return (w.astype(jnp.float32) * factor).astype(w.dtype)
    out = dict(weights, embedding=times("embedding", 1.0 / scale))
    if "unembed" not in weights:
        out["final_norm"] = times("final_norm", scale)
    return out


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx, self.mix, self.hp = ctx, ctx.mix, ctx.config
        #: the published vocabulary: the ids a user can send or get back.
        #: The program's table may hold more rows (padding), which are no
        #: token
        self.vocab = self.hp["vocab_size"]

    # -- set-up ------------------------------------------------------------

    def setup(self) -> None:
        from repro.launch.mesh import host_pod_backend
        from repro.models import registry

        cfg = self.ctx.program_config()
        self.cfg = cfg
        shapes = registry.abstract_params(cfg)[0]
        ref = self.ctx.reference
        rows = shapes["embedding"].shape[0]
        self.ref_init = jax.jit(lambda key: ref.init_weights(key, self.hp,
                                                             rows))
        mine = jax.tree.map(lambda a: (a.shape, a.dtype),
                            jax.eval_shape(self.ref_init, self.ctx.key(0)))
        theirs = jax.tree.map(lambda a: (a.shape, a.dtype), shapes)
        if mine != theirs:
            raise RuntimeError(f"the program's weights are laid out as "
                               f"{theirs}, the reference's as {mine}")
        if self.vocab > rows:
            raise RuntimeError(f"vocab_size {self.vocab} is more than the "
                               f"program's {rows} rows")
        scale = input_scale(cfg)
        self.params = jax.jit(lambda key: reparametrize(
            self.ref_init(key), scale))(self.ctx.key(0))
        self.backend = host_pod_backend(self.ctx.devices)
        self.traffic = traffic_lib.Traffic(self.mix, self.ctx.seed, self.vocab)
        #: batches in one cycle of the traffic: the window ends on one
        self.cycle = self.mix["levels"]

    def warm(self) -> None:
        """Serves one batch of the cell's own shapes, restart included, so
        that every program the window runs is loaded.  The engine slices its
        padded prompt batch once per position, a program per prompt shape:
        those are made here too."""
        b = self.mix["requests_per_batch"]
        for p in traffic_lib.prompt_levels(self.mix):
            jnp.asarray(np.zeros((b, p), np.int32))[:, 0:1].block_until_ready()
        self._serve(self.traffic.warm())

    # -- the window ----------------------------------------------------------

    def _serve(self, batch) -> dict:
        from repro.launch.serve import serve_with_early_restart
        from repro.serving.engine import Request

        reqs = [Request(uid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(batch.prompts,
                                               batch.new_tokens))]
        marks: list[float] = []

        def on_restart(_msg: str) -> None:
            marks.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("chipbench.restart"):
                pass

        t_issue = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.batch"):
            res = serve_with_early_restart(
                self.cfg, self.params, reqs, backend=self.backend,
                max_context=self.mix["max_context"],
                partition_gb=self.mix["start_slice_gb"], log=on_restart)
            jax.block_until_ready(res.engine.prompt_logits)
        t_return = time.perf_counter()
        # the served first tokens: the argmax at the last prompt position
        # over every row of the program's table, as the engine feeds it
        # forward; one past the published vocabulary is served wrong.  Only
        # they leave the batch; a batch's logits kept on the device through
        # the window fragment its memory
        first = np.asarray(jnp.argmax(
            res.engine.prompt_logits[:, -1, :self.cfg.vocab], axis=-1))
        return {"t_issue": t_issue, "t_return": t_return, "restarts": marks,
                "requests": res.requests, "prompt_len": batch.prompts.shape[1],
                "rows": len(reqs), "first_tokens": first}

    def run_batch(self, i: int) -> dict:
        return self._serve(self.traffic.batch(i))

    @staticmethod
    def latencies(rec: dict) -> list[float]:
        """Every request of a batch waits from its issue to its return."""
        return [rec["t_return"] - rec["t_issue"]] * rec["rows"]

    @staticmethod
    def tokens(rec: dict) -> int:
        return sum(r.max_new_tokens for r in rec["requests"])

    def useful_flops(self, rec: dict) -> float:
        """Model operations of every request's prompt and asked-for tokens."""
        f = self.ctx.cost.token_flops
        return sum(f(self.hp, pos) for r in rec["requests"]
                   for pos in range(len(r.prompt) + r.max_new_tokens))

    # -- the trace -------------------------------------------------------------

    def trace_steps(self, traced: list[dict], events: dict, dev: dict,
                    step: str, t0: float, t1: float) -> list[dict]:
        """Each execution of the step program in the traced batches, with
        its batch rows, its position and its phase.  An attempt starts at
        its batch's span or at a restart mark; positions count from 0 in
        each attempt."""
        import trace_reduce as tr
        batches = tr.spans(events, "batch")
        restarts = [s for s, _ in tr.spans(events, "restart")]
        execs = tr.executions(dev, step, t0, t1)
        out = []
        for rec, (b0, b1) in zip(traced, batches):
            starts = [b0] + [m for m in restarts if b0 <= m < b1]
            for attempt, a0 in enumerate(starts):
                a1 = starts[attempt + 1] if attempt + 1 < len(starts) else b1
                mine = [e for e in execs if a0 <= e[0] < a1]
                for pos, (s, d) in enumerate(mine):
                    out.append({"start": s, "dur": d, "rows": rec["rows"],
                                "position": pos, "attempt": attempt,
                                "phase": ("replay" if pos < rec["prompt_len"]
                                          else "decode")})
        return out

    # -- correctness -------------------------------------------------------------

    def incomplete(self, rec: dict) -> int:
        """Requests that did not get exactly the tokens they asked for, or
        got an id outside the published vocabulary (a padding row), first
        token included.  A request past the rows of the batch's logits got
        no first token."""
        first = rec["first_tokens"]
        return sum(i >= len(first) or len(r.generated) != r.max_new_tokens
                   or not all(0 <= t < self.vocab
                              for t in [first[i], *r.generated])
                   for i, r in enumerate(rec["requests"]))

    def _sample(self, records: list[dict]):
        """``check_requests`` finished requests drawn from the seed, the
        longest among them, each with the tokens the program served: the
        argmax at its last prompt position, then what it generated."""
        done = [(rec, i) for rec in records
                for i, r in enumerate(rec["requests"])
                if len(r.generated) == r.max_new_tokens]
        if not done:
            return []
        size = [len(rec["requests"][i].prompt) + rec["requests"][i]
                .max_new_tokens for rec, i in done]
        longest = int(np.argmax(size))
        rng = np.random.default_rng([self.ctx.seed, 2])
        rest = [j for j in range(len(done)) if j != longest]
        k = min(self.mix["check_requests"] - 1, len(rest))
        pick = [longest] + [rest[j] for j in rng.choice(len(rest), k,
                                                        replace=False)]
        out = []
        for j in pick:
            rec, i = done[j]
            r = rec["requests"][i]
            out.append((r.prompt, [int(rec["first_tokens"][i])]
                        + list(r.generated)))
        return out

    def _reference(self, precision: str):
        fns = self.__dict__.setdefault("_ref_fns", {})
        if precision not in fns:
            fns[precision] = self.ctx.reference.make_logits_at(self.hp,
                                                               precision)
        return fns[precision]

    def _ref_inputs(self, sample):
        t_max = max(traffic_lib.prompt_levels(self.mix)) \
            + self.mix["new_tokens"][1]
        k_max = self.mix["new_tokens"][1] + 1
        n = self.mix["check_requests"]
        toks = np.zeros((n, t_max), np.int32)
        pos = np.zeros((n, k_max), np.int32)
        for row, (prompt, served) in enumerate(sample):
            seq = np.concatenate([prompt, served[:-1]])
            toks[row, :len(seq)] = seq
            pos[row, :len(served)] = len(prompt) - 1 + np.arange(len(served))
        return jnp.asarray(toks), jnp.asarray(pos)

    def check(self, records: list[dict], control: bool = False) -> dict:
        """The widest gap by which a served token's logit lies below the
        reference's best, over a sample of finished requests.  With
        ``control`` the tokens compared are those that the reference
        computed in the control's precision puts first, at the same
        positions of the same prompts and served tokens, and the program's
        own gap is kept beside them as ``program_gap``.  A served id
        outside the published vocabulary makes the gap infinite.  The
        program's weights are freed first; the reference makes its own from
        the seed."""
        sample = self._sample(records)
        if not sample:
            return {"widest_gap": float("inf"), "served_tokens": 0}
        del self.params
        weights = self.ref_init(self.ctx.key(0))
        toks, pos = self._ref_inputs(sample)
        logits = np.asarray(self._reference("f32")(weights, toks, pos))
        got = np.concatenate([logits[row, :len(s)]
                              for row, (_, s) in enumerate(sample)])
        served = np.concatenate([np.asarray(s) for _, s in sample])
        out = {"widest_gap": widest_gap(got, served),
               "served_tokens": len(served)}
        if control:
            low = np.asarray(self._reference("fp8")(weights, toks, pos))
            picks = np.concatenate([low[row, :len(s)].argmax(-1)
                                    for row, (_, s) in enumerate(sample)])
            out["program_gap"] = out["widest_gap"]
            out["widest_gap"] = widest_gap(got, picks)
        return out
