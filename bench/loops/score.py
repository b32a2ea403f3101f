"""Offline scoring: a corpus cut into windows of ``seq`` tokens, scored
``rows`` windows a call by the program's full-sequence forward
(``repro.models.forward``) through the configuration's GEMM engine.  A
call returns the log-probability of every next token, as a perplexity
evaluation or a classifier reading scores wants them.

Mix keys (``bench/traffic/<name>.json``):

    loop      "score"
    rows      windows per call
    seq       tokens per window
    windows   the corpus, in windows; a run that scores them all before
              its window closes is refused
    ahead_s   seconds of calls kept dispatched ahead of the one the host
              waits for

The corpus is seeded token ids, every seed the same sizes.  Calls are
dispatched ahead, so that the chip stays fed while the host stands
still: as many as the warm-up's second call says fill ``ahead_s``.
When the window's time is up nothing more is sent, every call sent is
waited for, and the window closes after that wait, so that all the work
counts over all its time.  Each call is timed on the host from its
submission until the host sees its result on the device, and the
results stay there until the window has closed.  The check then
compares a seeded sample of the scored windows, every position of each,
with the plain reference.
"""
from __future__ import annotations

import math
import time
from collections import deque

import jax
import numpy as np

from harness.record import Record, Step

FIRST_TOKEN_ID = 2   # ids 0 and 1 are pad and eos by the program's default
ANN_CALL = "bench.call"   # the host dispatching a call
ANN_WAIT = "bench.wait"   # the host waiting for the oldest call sent
GAP = "max_logprob_gap"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator per (seed, purpose); any whole seed, 64 bits or not."""
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def corpus(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """(windows, rows, seq) token ids, from the seed."""
    shape = (int(mix["windows"]), int(mix["rows"]), int(mix["seq"]))
    return rng_for(seed, 0).integers(FIRST_TOKEN_ID, vocab, shape,
                                     dtype=np.int32)


def scorer(cfg, engine):
    """The timed program: tokens (rows, seq) -> log-probabilities of
    tokens[:, 1:] (rows, seq - 1), in float32."""
    import jax.numpy as jnp

    from repro.models import forward

    def score(params, tokens):
        logits, _ = forward(params, cfg, {"tokens": tokens}, engine)
        logits = logits[:, :-1]
        gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return gold - jax.nn.logsumexp(logits, -1)
    return jax.jit(score)


class Job:
    def __init__(self, cell, cfg, params, s, seed, reference):
        """``reference(params, tokens, s, mode)``: the family's plain
        reference (``bench/families/<family>.py``)."""
        from repro.models import DotEngine

        self.mix, self.s, self.params = cell.mix, s, params
        self.reference = reference
        self.seq = int(cell.mix["seq"])
        self.corpus = corpus(cell.mix, seed, s.vocab)
        self.fn = scorer(cfg, DotEngine(**cell.model["engine"]))
        self.outs: list = []
        self.depth = 1

    def warm(self) -> None:
        """The program compiled or loaded, then one call timed, which
        sets how many calls fill ``ahead_s``."""
        jax.block_until_ready(self.fn(self.params, self.corpus[0]))
        t0 = time.perf_counter()
        jax.block_until_ready(self.fn(self.params, self.corpus[1]))
        self.depth = max(1, math.ceil(float(self.mix["ahead_s"])
                                      / (time.perf_counter() - t0)))

    def window(self, seconds: float, on_open, on_close) -> Record:
        clock = time.perf_counter
        rec = Record()
        sent: deque = deque()   # (submitted at, result) in the order sent
        segments = [(self.seq, 0, True)] * self.corpus.shape[1]

        def retire():
            t0, out = sent.popleft()
            with jax.profiler.TraceAnnotation(ANN_WAIT):
                out.block_until_ready()
            rec.steps.append(Step(t0, clock(), segments))

        on_open()
        rec.t_open = clock()
        end = rec.t_open + seconds
        while clock() < end:
            i = len(self.outs)
            if i >= len(self.corpus):
                raise RuntimeError("the corpus ran dry before the window "
                                   "closed: give the mix more windows")
            t0 = clock()
            with jax.profiler.TraceAnnotation(ANN_CALL):
                out = self.fn(self.params, self.corpus[i])
            sent.append((t0, out))
            self.outs.append(out)
            if len(sent) >= self.depth:
                retire()
        while sent:
            retire()
        rec.t_close = clock()
        on_close()
        return rec

    def attempted(self) -> int:
        return len(self.outs) * self.corpus.shape[1]

    def free(self) -> None:
        self.outs = [np.asarray(o) for o in self.outs]
        self.fn = None

    def check(self, n_sample: int, seed: int, limit, control: bool) -> dict:
        """The widest gap between a scored log-probability and the
        reference's, over every position of a seeded sample of the
        window's scored windows; with ``control``, also the float8
        reference's gap, at the same positions."""
        import jax.numpy as jnp

        cells = [(i, r) for i in range(len(self.outs))
                 for r in range(self.corpus.shape[1])]
        pick = rng_for(seed, 2).permutation(len(cells))[:n_sample]
        def widest(a, b) -> float:
            g = float(np.max(np.abs(a - b)))
            return g if np.isfinite(g) else float("inf")

        worst = worst_ctl = 0.0
        n_tok = 0
        for k in sorted(pick):
            i, r = cells[k]
            toks = jnp.asarray(self.corpus[i, r])
            want = np.asarray(self.reference(self.params, toks, self.s,
                                             "f32"))
            worst = max(worst, widest(self.outs[i][r], want))
            if control:
                got8 = np.asarray(self.reference(self.params, toks, self.s,
                                                 "fp8"))
                worst_ctl = max(worst_ctl, widest(got8, want))
            n_tok += want.size
        out = {"rows": len(pick), "tokens": n_tok, GAP: worst}
        if control:
            out["control_" + GAP] = worst_ctl
        out["correct"] = bool(len(pick)) and limit is not None \
            and worst <= limit
        return out
