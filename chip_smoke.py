"""Serve qwen3-1.7B at its published widths on one TPU chip, and check
what comes out.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # 2x2 (data, model) mesh parity only

One process, one chip.  The phases:

(a) the device is a TPU; anything else fails (no CPU fallback);
(b) the serve CLI (``repro.launch.serve.main``) with the paged pool and
    continuous batching: XLA's dot for every GEMM, the Pallas
    paged-attention kernel for decode attention;
(c) the same requests through ``ServeLoop`` with
    ``DotEngine(schedule="morton")``: every projection and the vocab
    head on the SFC GEMM kernel;
(d) the serve CLI with ``--objective time``: every GEMM resolved by the
    tuner into a fresh cache file, winners printed.

The logits that decided each request's first token are compared, in
f32, between (b) and a plain full-sequence forward of the same tokens
(no cache, no paging, no Pallas), and between (c), (d) and (b).  A
mismatch, an exception, a fallback event or a degraded loop exits
non-zero.  The last line of a passing run is one JSON object naming the
device.  Weights are random, from ``SEED``.

``--four-chips`` runs only what exists across chips, on a (data, model)
mesh of the four chips of one host, at the published widths with depth
cut to 4 layers: sharded train-step loss == single-device loss, and the
kv-head-sharded paged pool == the replicated pool.

Seconds printed here are wall-clock seconds of one cold run, not a
benchmark.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"
ARCH = "qwen3_1_7b"
SEED = 0
REQUESTS, PROMPT_LEN, SHARED_PREFIX, MAX_NEW = 6, 64, 32, 16
SLOTS, CACHE_LEN, PAGE_SIZE = 4, 256, 16
SERVE_ARGV = ["--arch", ARCH, "--layout", "paged", "--mode", "continuous",
              "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
              "--max-new", str(MAX_NEW), "--slots", str(SLOTS),
              "--cache-len", str(CACHE_LEN), "--page-size", str(PAGE_SIZE),
              "--seed", str(SEED)]
# Tolerance on first-token logits, relative to the largest |logit| of
# the run compared against.  Every path computes in bf16 with f32
# accumulation, but rounds to bf16 at different points (XLA's dot vs the
# kernel's blocked k loop; a cache vs a full-sequence forward), so two
# correct runs differ by rounding noise.  Each layer rounds about nine
# times (q, k, v, attention, out-projection, gate, up, down, norms); 28
# layers give ~250 roundings of relative size u = 2**-8, and noise of
# that many independent roundings grows like sqrt(250) * u, about 16u =
# 2**-4.  A wrong tile, page or head moves logits by their own size,
# 16x more.
REL_TOL = 2.0 ** -4


def _phase_prompts(vocab: int) -> list[list[int]]:
    """Requests 0 and 4 share a SHARED_PREFIX-token prefix: request 4 is
    admitted after request 0 has registered its pages, so it adopts
    them through the prefix index."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(2, vocab, size=PROMPT_LEN).tolist()
               for _ in range(REQUESTS)]
    prompts[4][:SHARED_PREFIX] = prompts[0][:SHARED_PREFIX]
    return prompts


class _Compiles:
    """When JAX traced, lowered and compiled, and its persistent-cache
    hits, from its own monitoring events.  Traces nest (every jitted
    helper traced inside a step fires its own event), so compile
    seconds are the length of the union of the spans, not their sum."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration", BACKEND)

    def __init__(self):
        import jax

        self.spans: list[tuple[str, float, float]] = []
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((event, start, end))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def seconds(self, t0: float, t1: float) -> tuple[float, float]:
        """(all compile seconds, XLA backend compile seconds) in
        [t0, t1] of ``time.time()``."""
        inside = sorted((max(s, t0), min(e, t1), ev)
                        for ev, s, e in self.spans if e > t0 and s < t1)
        union, reached = 0.0, t0
        for s, e, _ in inside:
            if e > reached:
                union += e - max(s, reached)
                reached = e
        return union, sum(e - s for s, e, ev in inside if ev == self.BACKEND)


@contextlib.contextmanager
def _phase(name: str, compiles: _Compiles):
    h0, t0 = compiles.cache_hits, time.time()
    yield
    t1 = time.time()
    comp, backend = compiles.seconds(t0, t1)
    print(f"[{name}] compile {comp:.1f} s (trace, lower and XLA; XLA "
          f"{backend:.1f} s; {compiles.cache_hits - h0} compile-cache "
          f"hits), rest {t1 - t0 - comp:.1f} s (weight init, running, "
          f"host work; wall, not a benchmark)", flush=True)


def _recording_loop_class():
    """A ServeLoop that keeps the logits row which decided each
    request's first token, and its jitted decode step for the HLO
    check.  Under continuous batching the decode step is the only
    caller of ``_step``."""
    import numpy as np

    from repro.launch.serve import ServeLoop

    class RecordingLoop(ServeLoop):
        def _build_jits(self):
            super()._build_jits()
            self.first_logits = getattr(self, "first_logits", {})
            self.decode_jit = step = self._step

            def recording_step(p, s, t, pos, mask):
                logits, state = step(p, s, t, pos, mask)
                rows = np.asarray(logits[:, 0], np.float32)
                for slot in np.flatnonzero(self.active):
                    r = self.slot_req[slot]
                    if r not in self.first_token_s:
                        self.first_logits.setdefault(r, rows[slot])
                return logits, state

            self._step = recording_step

    return RecordingLoop


def _serve_cli(argv, prompts, loop_cls):
    """``repro.launch.serve.main`` building ``loop_cls`` loops."""
    from repro.launch import serve

    plain = serve.ServeLoop
    serve.ServeLoop = loop_cls
    try:
        return serve.main(argv, prompts=prompts)
    finally:
        serve.ServeLoop = plain


def _check_loop(name: str, loop) -> int:
    """Print and check the kernel evidence of a drained loop; returns
    the number of tpu_custom_call ops in its compiled decode step."""
    import jax.numpy as jnp

    from repro.kernels import paged_attention as pa

    hlo = loop.decode_jit.lower(
        loop.params, loop.state, jnp.zeros((loop.slots, 1), jnp.int32),
        jnp.asarray(loop.pos), jnp.asarray(loop.active)).compile().as_text()
    n_custom = hlo.count('custom_call_target="tpu_custom_call"')
    degraded = loop.c_degraded.value
    print(f"[{name}] engine schedule={loop.engine.schedule}, decode step "
          f"tpu_custom_call x{n_custom}, FALLBACK_EVENTS "
          f"{len(pa.FALLBACK_EVENTS)}, serve.degraded {degraded:g}",
          flush=True)
    if n_custom == 0:
        raise SystemExit(f"[{name}] no Pallas kernel in the decode step")
    if pa.FALLBACK_EVENTS or degraded or loop._kernel_degraded:
        raise SystemExit(f"[{name}] the kernel degraded: "
                         f"{pa.FALLBACK_EVENTS}")
    missing = set(range(REQUESTS)) - set(loop.first_logits)
    if missing or loop.errors:
        raise SystemExit(f"[{name}] requests without a first token "
                         f"{sorted(missing)}, failed {loop.errors}")
    return n_custom


def _compare(name: str, got: dict, want: dict, vocab: int,
             tokens: tuple | None = None) -> None:
    """First-token logits of ``got`` vs ``want`` in f32 under REL_TOL;
    ``tokens`` = (got_out, want_out, prompts) also reports the share of
    generated greedy tokens that agree."""
    import numpy as np

    worst = 0.0
    for r in range(REQUESTS):
        a = np.asarray(got[r][:vocab], np.float32)
        b = np.asarray(want[r][:vocab], np.float32)
        if not np.isfinite(a).all():
            raise SystemExit(f"[{name}] non-finite logits, request {r}")
        diff = float(np.max(np.abs(a - b)))
        scale = float(np.max(np.abs(b)))
        worst = max(worst, diff / scale)
        print(f"[{name}] request {r}: max |diff| {diff:.4g} over max "
              f"|logit| {scale:.4g} (rel {diff / scale:.3g}), first token "
              f"{int(a.argmax())} vs {int(b.argmax())}")
    if tokens is not None:
        g, w, prompts = tokens
        pairs = [(x, y) for r, p in enumerate(prompts)
                 for x, y in zip(g[r][len(p):], w[r][len(p):])]
        agree = sum(x == y for x, y in pairs) / len(pairs)
        print(f"[{name}] greedy tokens that agree: {agree:.3f} of "
              f"{len(pairs)}")
    print(f"[{name}] worst rel diff {worst:.3g} (tolerance {REL_TOL:g})",
          flush=True)
    if worst > REL_TOL:
        raise SystemExit(f"[{name}] logits mismatch: {worst:.3g} > "
                         f"{REL_TOL:g}")


def _reference_logits(params, cfg, prompts) -> dict:
    """The plain full-sequence forward of what each request's first
    decode step sees: the prompt, then its last token again at position
    len(prompt) (the serve loop's discipline), attended as one q chunk
    (the config's chunk must divide the sequence)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward

    toks = jnp.asarray([p + [p[-1]] for p in prompts], jnp.int32)
    one_chunk = dataclasses.replace(cfg, attn_q_chunk=toks.shape[1])
    logits, _ = jax.jit(lambda p, t: forward(p, one_chunk, {"tokens": t}))(
        params, toks)
    last = np.asarray(logits[:, -1], np.float32)
    return {r: last[r] for r in range(len(prompts))}


def _tuner_winners(path: Path) -> None:
    entries = json.loads(path.read_text())["entries"]
    gemm = {k: e for k, e in entries.items() if not k.startswith("attn")}
    n_pallas = sum(e["config"]["schedule"] != "xla" for e in gemm.values())
    for key, e in sorted(entries.items()):
        c = e["config"]
        print(f"[d] winner {key} -> {c.get('schedule', c)} "
              f"{c.get('bm')}x{c.get('bn')}x{c.get('bk')}")
    print(f"[d] {len(gemm)} GEMM winners, {n_pallas} on Pallas "
          f"({len(entries) - len(gemm)} attention entries)", flush=True)


def one_chip(compiles: _Compiles) -> None:
    import os

    import jax

    from repro.configs import get_config
    from repro.models import DotEngine
    from repro.serve import ServeConfig

    cfg = get_config(ARCH)
    prompts = _phase_prompts(cfg.vocab)
    loop_cls = _recording_loop_class()

    with _phase("b", compiles):
        loop_b = _serve_cli(SERVE_ARGV, prompts, loop_cls)
        n_b = _check_loop("b", loop_b)
    params = loop_b.params
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[b] parameter bytes {n_bytes} ({cfg.n_layers} layers, "
          f"d={cfg.d_model}, vocab {cfg.vocab}, {cfg.param_dtype})",
          flush=True)
    first_b, out_b = loop_b.first_logits, loop_b.out
    del loop_b

    with _phase("ref", compiles):
        ref = _reference_logits(params, cfg, prompts)
    _compare("b vs forward", first_b, ref, cfg.vocab)

    with _phase("c", compiles):
        sc = ServeConfig(slots=SLOTS, cache_len=CACHE_LEN, seed=SEED,
                         layout="paged", page_size=PAGE_SIZE,
                         mode="continuous")
        loop_c = loop_cls(cfg, params, sc,
                          engine=DotEngine(schedule="morton"))
        for r, p in enumerate(prompts):
            loop_c.submit(r, p)
        loop_c.run(max_new=MAX_NEW)
        n_c = _check_loop("c", loop_c)
    if n_c <= n_b:
        raise SystemExit("[c] the SFC GEMM kernel is not in the decode "
                         "step")
    _compare("c vs b", loop_c.first_logits, first_b, cfg.vocab,
             (loop_c.out, out_b, prompts))
    del loop_c, params

    tune = OUT / "tune.json"
    tune.unlink(missing_ok=True)
    os.environ["REPRO_TUNE_CACHE"] = str(tune)
    with _phase("d", compiles):
        loop_d = _serve_cli(SERVE_ARGV + ["--objective", "time"], prompts,
                            loop_cls)
        _check_loop("d", loop_d)
    _tuner_winners(tune)
    _compare("d vs b", loop_d.first_logits, first_b, cfg.vocab,
             (loop_d.out, out_b, prompts))


def four_chips(compiles: _Compiles) -> None:
    from repro.configs import get_config
    from repro.launch import selftest
    from repro.launch.mesh import make_smoke_mesh

    mesh = make_smoke_mesh((2, 2), ("data", "model"))
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=4)
    print(f"[4 chips] mesh {dict(mesh.shape)}; {ARCH} at published widths "
          f"with depth cut {full.n_layers} -> {cfg.n_layers} layers, so "
          f"the one-device train-step reference fits one chip", flush=True)
    with _phase("dp_tp_matches_single", compiles):
        selftest.check_dp_tp_matches_single(ARCH, mesh=mesh, cfg=cfg)
    with _phase("paged_sharded_matches_replicated", compiles):
        selftest.check_paged_sharded_matches_replicated(
            ARCH, mesh=mesh, cfg=cfg)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh parity checks")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{dev.platform!r}); this script never falls back "
                         f"to the CPU")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        raise SystemExit(f"chip_smoke: {want} chips needed, "
                         f"{len(devices)} found")
    print(f"[a] device_kind {dev.device_kind!r}, {len(devices)} device(s)",
          flush=True)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"[a] compile cache {cache} ({n_cached} entries before this run)",
          flush=True)
    OUT.mkdir(exist_ok=True)
    compiles = _Compiles()
    if args.four_chips:
        four_chips(compiles)
    else:
        one_chip(compiles)
    n_after = len(list(cache.glob("*"))) if cache.is_dir() else 0
    print(f"[a] compile cache {cache}: {n_after} entries after this run",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
