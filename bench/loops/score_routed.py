"""Offline scoring of a routed-expert model: the ``score`` loop, whose
timed program also returns each expert layer's choice of experts, so
that the check can hold a choice made within rounding of a tie apart
from a wrong one.

Mix keys: as ``score`` (``loop`` "score_routed").  Check keys
(``bench/checks/<workload>.json``): ``sample`` and ``max_logprob_gap``
as ``score``, and ``max_route_miss_margin``.

Routing is a discrete choice: where the reference's k-th and (k+1)-th
biased scores lie closer together than the program's rounding moves
them, a sound program may choose the other expert, and a whole
expert's output then moves its token.  So the check runs the family's
reference (``reference_routed``) on the program's own choices, and
compares two numbers with their limits:

    max_logprob_gap         the widest gap between a scored
                            log-probability and the reference's, the
                            reference running the program's experts
    max_route_miss_margin   the widest margin (the reference's k-th
                            biased score less its (k+1)-th) among the
                            (token, layer) pairs where the program's
                            set of experts is not the reference's own

A program that rounds no worse than the configuration's precision
chooses otherwise only near ties, and computes what it chose to the
reference's rounding.  With ``control`` the float8 reference stands in
for the program, its own choices taken as the program's.
"""
from __future__ import annotations

import sys
from typing import NamedTuple

import jax
import numpy as np

from harness.cell import loop_module

SCORE = loop_module("score")
GAP = SCORE.GAP
MARGIN = "max_route_miss_margin"
corpus, rng_for = SCORE.corpus, SCORE.rng_for


class Scored(NamedTuple):
    """One call's result: log-probabilities (rows, seq - 1) and each
    expert layer's choice (rows, expert layers, seq, k)."""
    logprobs: jax.Array
    routes: jax.Array

    def block_until_ready(self):
        jax.block_until_ready(tuple(self))
        return self


def scorer(cfg, engine):
    """The timed program: tokens (rows, seq) -> ``Scored``."""
    import jax.numpy as jnp

    from repro.models import forward

    def score(params, tokens):
        logits, _, routes = forward(params, cfg, {"tokens": tokens}, engine,
                                    routes=True)
        logits = logits[:, :-1]
        gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return gold - jax.nn.logsumexp(logits, -1), \
            jnp.moveaxis(routes, 0, 1)
    fn = jax.jit(score)
    return lambda params, tokens: Scored(*fn(params, tokens))


def route_misses(routes, own, margin) -> dict:
    """Where the experts run (layers, L, k) are not the reference's own
    choice (layers, L, k): how many (token, layer) pairs, and the
    widest of their margins (layers, L)."""
    miss = (np.sort(routes, -1) != np.sort(own, -1)).any(-1)
    return {"misses": int(miss.sum()), "pairs": int(miss.size),
            MARGIN: float(np.max(margin[miss], initial=0.0))}


class Job(SCORE.Job):
    def __init__(self, cell, cfg, params, s, seed, reference):
        super().__init__(cell, cfg, params, s, seed, reference)
        from repro.models import DotEngine

        self.routed = cell.family().reference_routed
        self.margin_limit = cell.check.get(MARGIN)
        self.fn = scorer(cfg, DotEngine(**cell.model["engine"]))
        self.last_check: dict = {}

    def free(self) -> None:
        self.outs = [Scored(*(np.asarray(a) for a in o)) for o in self.outs]
        self.fn = None

    def _against_reference(self, toks, lp, routes) -> dict:
        """The reference run on ``routes``: the widest log-probability
        gap to ``lp``, and the route misses."""
        want, own, margin = (np.asarray(a) for a in self.routed(
            self.params, toks, self.s, "f32", routes))
        g = float(np.max(np.abs(lp - want)))
        return {GAP: g if np.isfinite(g) else float("inf"),
                **route_misses(routes, own, margin)}

    def check(self, n_sample: int, seed: int, limit, control: bool) -> dict:
        """Over every position of a seeded sample of the window's scored
        windows: the widest log-probability gap with the reference on
        the program's experts, and the widest margin of a route miss;
        with ``control``, the same of the float8 reference.  Also the
        share of the choices that went to the held experts."""
        import jax.numpy as jnp

        cells = [(i, r) for i in range(len(self.outs))
                 for r in range(self.corpus.shape[1])]
        pick = rng_for(seed, 2).permutation(len(cells))[:n_sample]
        s = self.s
        got = {GAP: 0.0, MARGIN: 0.0, "misses": 0, "pairs": 0}
        ctl = dict(got)
        held = n_tok = 0

        def fold(into, one):
            into[GAP] = max(into[GAP], one[GAP])
            into[MARGIN] = max(into[MARGIN], one[MARGIN])
            into["misses"] += one["misses"]
            into["pairs"] += one["pairs"]

        for k in sorted(pick):
            i, r = cells[k]
            toks = jnp.asarray(self.corpus[i, r])
            lp, routes = self.outs[i].logprobs[r], self.outs[i].routes[r]
            fold(got, self._against_reference(toks, lp, routes))
            held += int(((routes >= s.first_held)
                         & (routes < s.first_held + s.held)).sum())
            if control:
                lp8, own8, _ = (np.asarray(a) for a in self.routed(
                    self.params, toks, s, "fp8"))
                fold(ctl, self._against_reference(toks, lp8, own8))
            n_tok += lp.size
        out = {"rows": len(pick), "tokens": n_tok, **got,
               "held_share": held / max(1, got["pairs"] * s.topk)}
        if control:
            out.update({"control_" + k: v for k, v in ctl.items()})
        out["correct"] = bool(len(pick)) and limit is not None \
            and self.margin_limit is not None and got[GAP] <= limit \
            and got[MARGIN] <= self.margin_limit
        print(f"check {MARGIN} {got[MARGIN]!r} limit "
              f"{self.margin_limit!r}; route misses {got['misses']} of "
              f"{got['pairs']}; held share {out['held_share']!r}",
              file=sys.stderr, flush=True)
        self.last_check = out
        return out
