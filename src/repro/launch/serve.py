"""Batched serving driver with lockstep and continuous batching.

A fixed pool of decode slots over one shared KV cache.  Two schedulers
(:class:`repro.serve.ServeConfig.mode`):

* ``lockstep`` -- the historical loop: a request's whole prompt is
  prefilled at admission, live slots decode together.
* ``continuous`` -- requests join and leave mid-flight: prompts are
  prefilled in *chunks* interleaved into the decode stream under a
  bounded per-step token budget (``prefill_budget``), so a long prompt
  never stalls the slots that are already decoding (DESIGN.md §11).

Positions are per-slot vectors whenever the family allows it (attention
without SWA): each request advances on its own clock, so its tokens are
independent of co-resident slots and the two schedulers emit
byte-identical greedy tokens for the same arrival trace
(regression-tested).

``layout=KVLayout.PAGED`` swaps the per-slot ``cache_len`` strips for
the paged KV cache (DESIGN.md §10): Morton-ordered physical pages,
per-slot block tables, copy-free eviction, pool-bounded admission.
Under continuous batching the paged pool adds reference-counted
copy-on-write prefix sharing (DESIGN.md §11): slots whose prompts share
page-aligned prefixes map the *same physical pages* through a radix
index, a private copy is forked only on first write, and release
reclaims a page only at refcount zero.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b --smoke \
      --requests 6 --max-new 16 --layout paged --mode continuous
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import _engine_for
from repro.models import DotEngine, decode_step, \
    fused_epilogue_savings_bytes, init_decode_state, init_model
from repro.models.attention import refuse_mla
from repro.models.transformer import prefill_kv_chunk
from repro.obs import MetricsRegistry, Tracer, default_registry, \
    default_tracer, null_registry
from repro.power import EnergyMeter, EnergyReport, WorkloadHints, \
    detect_backend
from repro.runtime import ChaosInjector, InjectedFault, \
    ServeSnapshotter, StragglerMonitor, TransientFault, \
    parse_chaos_spec
from repro.runtime import chaos as _chaos
from repro.serve import KVLayout, ServeConfig
from repro.tune.cost import AttnSpec, attn_decode_bytes

# ServeLoop kwargs the pre-ServeConfig constructor took, mapped 1:1 onto
# ServeConfig fields (``paged`` maps onto ``layout``)
_LEGACY_KW = {"slots", "cache_len", "temperature", "eos_id", "seed",
              "objective", "paged", "page_size", "num_pages", "layout",
              "mode", "prefill_budget", "prefix_sharing"}


class ServeLoop:
    def __init__(self, cfg, params, config: ServeConfig | None = None, *,
                 engine: DotEngine | None = None, power_backend=None,
                 metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None,
                 chaos: ChaosInjector | str | None = None,
                 **legacy):
        if legacy:
            bad = set(legacy) - _LEGACY_KW
            if bad:
                raise TypeError(
                    f"unexpected ServeLoop arguments {sorted(bad)}")
            if config is not None:
                raise TypeError(
                    "pass either a ServeConfig or legacy keyword "
                    "arguments, not both")
            warnings.warn(
                "ServeLoop(slots=..., paged=..., ...) keyword arguments "
                "are deprecated; pass a repro.serve.ServeConfig",
                DeprecationWarning, stacklevel=2)
            paged = legacy.pop("paged", None)
            if paged is not None:
                if "layout" in legacy:
                    from repro.serve import resolve_layout
                    legacy["layout"] = resolve_layout(
                        legacy["layout"], paged)
                else:
                    legacy["layout"] = KVLayout.PAGED if paged \
                        else KVLayout.CONTIGUOUS
            config = ServeConfig(**legacy)
        sc = config if config is not None else ServeConfig()
        refuse_mla(cfg, "serving")
        self.config = sc
        self.cfg = cfg
        self.params = params
        self.slots = sc.slots
        self.cache_len = sc.cache_len
        self.engine = _engine_for(engine, sc.objective)
        self.objective = sc.objective or "time"
        self.mode = sc.mode
        self.layout = sc.layout
        self.paged = sc.paged
        self.page_size = sc.page_size
        self.prefill_budget = sc.prefill_budget
        # prefix sharing needs block tables (paged) and the mid-flight
        # admissions that make a shared prefix reachable (continuous)
        self.prefix_sharing = bool(
            sc.prefix_sharing and sc.paged and sc.mode == "continuous")
        # per-slot position vectors: each request on its own clock, its
        # tokens independent of co-resident slots (DESIGN.md §11).  SWA
        # rings and ssm states keep the historical shared-scalar lockstep.
        self._vector_pos = bool(cfg.has_attention and not cfg.has_ssm
                                and cfg.swa_window is None)
        if sc.mode == "continuous":
            if not cfg.has_attention or cfg.has_ssm:
                raise ValueError(
                    f"continuous batching needs a pure-attention family "
                    f"(chunked prefill), got {cfg.family!r}")
            if cfg.swa_window is not None:
                raise ValueError(
                    "continuous batching does not support SWA rings yet")
        self.attn_spec = AttnSpec("paged", sc.page_size) if sc.paged \
            else AttnSpec("contig")
        # DVFS hints for per-step energy accounting, resolved per shape
        # (ROADMAP "per-shape f_scale hints"): the projection GEMM
        # (slots x d x d, fused residual), the MLP up-projection
        # (slots x d_ff x d, fused silu) and the decode-attention step
        # under its own attn= keyspace can all tune to different
        # operating points; the report carries each.
        self.f_scales = {"proj": 1.0, "mlp": 1.0, "attn": 1.0}
        if sc.objective:
            from repro.tune import EpilogueSpec, GemmSpec, resolve
            # same dtype AND epilogue the engine's GEMMs resolve under
            # (bucket match): the decode step's projection executes with
            # a fused residual (.../ep=res), the MLP up-projection with a
            # fused silu (.../ep=silu) -- DESIGN.md §9.  All three route
            # through the unified tune.resolve entrypoint (DESIGN.md §11)
            self.f_scales["proj"] = resolve(
                GemmSpec(sc.slots, cfg.d_model, cfg.d_model,
                         cfg.act_dtype,
                         epilogue=EpilogueSpec(residual=True)),
                objective=sc.objective).f_scale
            self.f_scales["mlp"] = resolve(
                GemmSpec(sc.slots, cfg.d_ff or cfg.d_model, cfg.d_model,
                         cfg.act_dtype,
                         epilogue=EpilogueSpec(activation="silu")),
                objective=sc.objective).f_scale
            if cfg.has_attention:
                self.f_scales["attn"] = self._resolve_attn_f()
        # the dominant projection's point keeps the historical scalar
        self.f_scale = self.f_scales["proj"]
        self.temperature = sc.temperature
        self.eos_id = sc.eos_id
        self.rng = np.random.default_rng(sc.seed)
        if sc.paged:
            from repro.serve.paged_kv import init_paged_serving, \
                page_permutation
            # one constructor for allocator + device state: pool size
            # and block-table width must agree (DESIGN.md §10)
            self.alloc, self.state = init_paged_serving(
                cfg, sc.slots, sc.cache_len, page_size=sc.page_size,
                num_pages=sc.num_pages,
                prefix_sharing=self.prefix_sharing)
            self._perm_np = page_permutation(cfg.n_layers,
                                             self.alloc.num_pages)
        else:
            self.alloc = None
            self.state = init_decode_state(cfg, sc.slots, sc.cache_len)
        self.pos = np.zeros(sc.slots, np.int32)   # next position per slot
        self.active = np.zeros(sc.slots, bool)
        self.out: dict[int, list[int]] = {}
        self.slot_req = [-1] * sc.slots
        self.queue: list[tuple[int, list[int]]] = []
        # per-request generation budget survives preemption; admission
        # order picks the preemption victim (most recently admitted)
        self.request_emitted: dict[int, int] = {}
        self._admit_seq = [0] * sc.slots
        self._admit_counter = 0
        self.preemptions = 0
        # continuous-batching bookkeeping: a slot mid-prefill has
        # _prefill_len >= 0 (prompt length) and _prefill_done tokens
        # already written; _slot_prompt keeps the admitted prompt for
        # chunking, prefix registration and clone matching
        self._prefill_len = np.full(sc.slots, -1, np.int64)
        self._prefill_done = np.zeros(sc.slots, np.int64)
        self._slot_prompt: list[list[int] | None] = [None] * sc.slots
        # per-step prompt tokens actually prefilled (budget telemetry:
        # every entry is <= prefill_budget by construction, tested)
        self.prefill_tokens_per_step: list[int] = []
        # energy telemetry: one reading per prefill / prefill-chunk /
        # decode step, attributed to requests weighted by the tokens
        # each processed in it (a decode step is one token per live
        # slot, so its split is even; a shared prefill chunk is not)
        self.power = power_backend or detect_backend()
        # fused epilogues (DESIGN.md §9): modeled HBM bytes one decode
        # step over the full slot pool no longer moves
        self.ep_saved_step = fused_epilogue_savings_bytes(cfg, sc.slots)
        # modeled per-step HBM traffic, split attention-cache vs GEMM
        # (weights stream once per step) -- reported next to each other
        # so J/step is attributable to the cache layout (DESIGN.md §10)
        self._gemm_bytes_step = float(sum(
            p.size * np.dtype(p.dtype).itemsize
            for p in jax.tree.leaves(params)))
        self._cache_dtype_bytes = np.dtype(cfg.act_jdtype()).itemsize
        self.energy = EnergyReport(backend=self.power.name,
                                   meta={"driver": "serve",
                                         "slots": sc.slots,
                                         "mode": sc.mode,
                                         "objective": self.objective,
                                         "attn": self.attn_spec.tag(),
                                         "attn_share": 1.0,
                                         "f_scale": self.f_scale,
                                         "f_scale_per_shape":
                                         dict(self.f_scales),
                                         "attn_bytes_step":
                                         self._attn_bytes_step(),
                                         "gemm_bytes_step":
                                         self._gemm_bytes_step,
                                         "fused_epilogue_saved_bytes_step":
                                         self.ep_saved_step})
        self.request_joules: dict[int, float] = {}
        # --- observability (DESIGN.md §12) ---------------------------------
        # metrics default to the process registry (null when sc.obs is
        # off: every instrument becomes a shared no-op); the tracer
        # defaults to the process tracer, which is disabled until a
        # driver installs one (set_default_tracer / --trace), so span
        # recording costs nothing unless somebody asked for a trace.
        self._bind_obs(
            metrics if metrics is not None else (
                default_registry() if sc.obs else null_registry()),
            tracer if tracer is not None else (
                default_tracer() if sc.obs else Tracer(enabled=False)))
        # request lifecycle on the time.monotonic clock (seconds; trace
        # timestamps are the same clock in us): arrival at submit,
        # first decoded token, retirement -- TTFT/TPOT/e2e and SLO
        # attainment derive from these (ROADMAP SLO item)
        self.arrival_s: dict[int, float] = {}
        self.first_token_s: dict[int, float] = {}
        self.finish_s: dict[int, float] = {}
        self.request_ttft_ms: dict[int, float] = {}
        self.request_tpot_ms: dict[int, float] = {}
        self.request_e2e_ms: dict[int, float] = {}
        self.request_slo_ok: dict[int, bool] = {}
        # current lifecycle phase per request (queued/prefill/decode):
        # keeps the async phase spans balanced across preemption, which
        # bounces a request back to queued mid-decode
        self._req_phase: dict[int, str | None] = {}
        # live-share tuner feedback (satellite of DESIGN.md §12): the
        # lowest observed COW sharing ratio, and the 0.01-quantized tag
        # the attention winner was last resolved under
        self._min_share = 1.0
        self._share_tag: str | None = None
        self._revived_seen = 0
        self.g_share.set(1.0)
        self._tok_flops = 2.0 * sum(
            int(p.size) for p in jax.tree.leaves(params))
        # --- fault tolerance (DESIGN.md §14) -------------------------------
        # guards/deadline mirrored as attributes so the fault-tolerance
        # bench can toggle them on one loop instance (same jit cache)
        self.guards = sc.fault_guards
        self.deadline_ms = sc.deadline_ms
        self.errors: dict[int, str] = {}
        # requests whose retirement already hit the metrics/spans: a
        # snapshot restore can rewind a finished request into flight, so
        # its replayed retirement must not double-count
        self._finished: set[int] = set()
        self._iter = 0
        self._kernel_degraded = False
        self.straggler = StragglerMonitor()
        if chaos is None:
            chaos = sc.chaos
        if isinstance(chaos, str):
            chaos = parse_chaos_spec(chaos, seed=sc.seed)
        self.chaos = chaos
        # chaos runs need restore-and-replay to always be possible: an
        # injected fault mid-iteration leaves half-applied scheduler
        # state that only a rewind repairs -- default to snapshotting
        # every iteration unless the caller chose a cadence
        every = sc.snapshot_every or (1 if self.chaos is not None
                                      else None)
        self.snapshotter = ServeSnapshotter(
            self, every=every, root=sc.snapshot_dir) if every else None
        self._build_jits()

    def _build_jits(self) -> None:
        """(Re)build the jitted step wrappers.  Called again after an
        injected kernel-fault degradation: the fresh wrappers retrace,
        and the retrace dispatches through the now-sticky XLA
        fallback."""
        cfg = self.cfg
        self._step = jax.jit(
            lambda p, s, t, pos, mask: decode_step(
                p, cfg, s, t, pos, self.engine, row_mask=mask))
        self._chunk = jax.jit(
            lambda p, s, t, sl, st, ln: prefill_kv_chunk(
                p, cfg, s, t, sl, st, ln, self.engine))

    # ------------------------------------------------------------- obs ----
    def _bind_obs(self, metrics: MetricsRegistry, tracer: Tracer) -> None:
        """Bind the metrics registry + tracer and hand out this loop's
        instruments.  Constructor path; ``bench_obs_overhead`` rebinds
        at runtime to measure the enabled-vs-disabled delta on a single
        loop (one jit cache, one allocator, no cross-instance skew)."""
        self.metrics = m = metrics
        self.tracer = tracer
        self.m_ttft = m.histogram("serve.ttft_ms")
        self.m_tpot = m.histogram("serve.tpot_ms")
        self.m_e2e = m.histogram("serve.e2e_ms")
        self.m_step = m.histogram("serve.step_ms")
        self.m_prefill_tok = m.histogram("serve.prefill_tokens")
        self.c_submitted = m.counter("serve.requests.submitted")
        self.c_finished = m.counter("serve.requests.finished")
        self.c_preempt = m.counter("serve.preemptions")
        self.c_cow = m.counter("serve.cow_forks")
        self.c_scrubbed = m.counter("serve.pages.scrubbed")
        self.c_revived = m.counter("serve.pages.revived")
        self.c_slo_met = m.counter("serve.slo.met")
        self.c_slo_violation = m.counter("serve.slo.violations")
        self.g_queue = m.gauge("serve.queue.depth")
        self.g_occ = m.gauge("serve.pool.occupancy")
        self.g_hit_ratio = m.gauge("serve.prefix.hit_ratio")
        self.g_share = m.gauge("serve.attn.min_share")
        # fault tolerance (DESIGN.md §14)
        self.c_failed = m.counter("serve.requests.failed")
        self.c_shed = m.counter("serve.shed")
        self.c_retries = m.counter("serve.retries")
        self.c_restores = m.counter("serve.restores")
        self.c_degraded = m.counter("serve.degraded")
        self.h_restore_ms = m.histogram("serve.restore_ms")
        self._fault_counters: dict[str, object] = {}

    def _fault(self, point: str, **args) -> None:
        """Meter one observed/injected fault at ``point``: a
        ``serve.faults.<point>`` counter plus an instant trace event."""
        c = self._fault_counters.get(point)
        if c is None:
            c = self.metrics.counter(f"serve.faults.{point}")
            self._fault_counters[point] = c
        c.inc()
        self.tracer.instant(f"serve.faults.{point}", **args)

    # -------------------------------------------------- tuner feedback ----
    def _resolve_attn_f(self, share: float = 1.0) -> float:
        """DVFS point of the decode-attention winner under the layout the
        kernel actually runs.  ``share`` < 1 resolves under the live COW
        sharing keyspace (``.../attn=paged-p8-sX.XX``, DESIGN.md §11) so
        the winner's byte curve matches the gathered-once traffic;
        share=1 -- no sharing telemetry yet -- keeps the historical key."""
        from repro.tune import DecodeAttnSpec, resolve
        spec = self.attn_spec
        if share < 0.995:
            spec = dataclasses.replace(
                spec, share=max(0.01, round(share, 2)))
        return resolve(
            DecodeAttnSpec(self.slots, self.cache_len,
                           n_heads=self.cfg.n_heads,
                           n_kv_heads=self.cfg.n_kv_heads,
                           d_head=self.cfg.d_head,
                           dtype=self.cfg.act_dtype, attn=spec),
            objective=self.config.objective).f_scale

    def _observe_share(self, share: float) -> None:
        """Feed the live sharing ratio back into telemetry and, when it
        crosses into a new 0.01-quantized bucket, re-resolve the
        decode-attention winner under that keyspace (ROADMAP item: the
        loop now *reports and retunes* on observed share, rather than
        resolving once under the share=1 fallback)."""
        if share >= self._min_share:
            return
        self._min_share = share
        self.g_share.set(share)
        tag = f"{max(0.01, round(share, 2)):.2f}"
        if self.config.objective and tag != self._share_tag \
                and self.cfg.has_attention:
            self._share_tag = tag
            self.f_scales["attn"] = self._resolve_attn_f(share)
            self.energy.meta["f_scale_per_shape"] = dict(self.f_scales)

    # ---------------------------------------------- lifecycle accounting --
    def _set_phase(self, req_id: int, phase: str | None) -> None:
        """Move a request between lifecycle phases, keeping one async
        span (``request.<phase>``) open per request at all times --
        begin/end stay balanced even when preemption bounces a request
        from decode back to queued."""
        prev = self._req_phase.get(req_id)
        if prev:
            self.tracer.end_async(f"request.{prev}", req_id)
        self._req_phase[req_id] = phase
        if phase:
            self.tracer.begin_async(f"request.{phase}", req_id)

    def _finish_request(self, req_id: int,
                        error: str | None = None) -> None:
        """Retirement accounting: TTFT / TPOT / e2e histograms, SLO
        attainment against ``config.latency_slo_ms`` (TTFT target), and
        the request's enclosing async span closed with its totals.
        ``error`` retires a *failed* request (NaN quarantine, deadline,
        shed): it counts on ``serve.requests.failed`` and skips the
        latency/SLO accounting.  A snapshot restore can rewind a
        finished request back into flight; its replayed retirement is
        detected via ``_finished`` and left out of metrics + spans."""
        repeat = req_id in self._finished
        self._finished.add(req_id)
        now = time.monotonic()
        self.finish_s[req_id] = now
        n_out = self.request_emitted.get(req_id, 0)
        ttft = tpot = slo_ok = None
        if repeat:
            pass           # replayed retirement: no double accounting
        elif error is not None:
            self.c_failed.inc()
        else:
            self.c_finished.inc()
            arr = self.arrival_s.get(req_id)
            first = self.first_token_s.get(req_id)
            if arr is not None and first is not None:
                ttft = (first - arr) * 1e3
                self.request_ttft_ms[req_id] = ttft
                self.m_ttft.observe(ttft)
                e2e = (now - arr) * 1e3
                self.request_e2e_ms[req_id] = e2e
                self.m_e2e.observe(e2e)
            if first is not None and n_out > 1:
                tpot = (now - first) * 1e3 / (n_out - 1)
                self.request_tpot_ms[req_id] = tpot
                self.m_tpot.observe(tpot)
            slo = self.config.latency_slo_ms
            if slo is not None and ttft is not None:
                slo_ok = bool(ttft <= slo)
                self.request_slo_ok[req_id] = slo_ok
                (self.c_slo_met if slo_ok else self.c_slo_violation).inc()
        self._set_phase(req_id, None)
        if not repeat:
            self.tracer.end_async(
                "request", req_id, tokens=n_out,
                joules=self.request_joules.get(req_id, 0.0),
                ttft_ms=ttft, tpot_ms=tpot, slo_ok=slo_ok,
                error=error)

    def _finish_error(self, req_id: int, reason: str) -> None:
        """Finish a request *with an error* instead of requeueing it:
        the caller has already detached it from any slot/queue."""
        self.errors[req_id] = reason
        self.tracer.instant("serve.request.failed", req=req_id,
                            reason=reason)
        self._finish_request(req_id, error=reason)

    def _fail_slot(self, slot: int, reason: str) -> None:
        """Evict a busy slot's request and finish it with ``reason``
        (NaN quarantine / deadline): deactivate, drop prefill state,
        release its page references, retire with an error -- co-resident
        slots never notice."""
        req = self.slot_req[slot]
        self.active[slot] = False
        self._prefill_len[slot] = -1
        self._prefill_done[slot] = 0
        self._slot_prompt[slot] = None
        if self.paged:
            self.alloc.release(slot)
            self._sync_tables()
        self._finish_error(req, reason)

    def _pump_gauges(self) -> None:
        """Per-step gauge refresh: queue depth, page-pool occupancy,
        prefix-index hit ratio, plus the scrubbed-vs-revived page reuse
        counters (revived pages skip the zeroing scrub -- the delta here
        tracks how often the cached FIFO pays off, DESIGN.md §11)."""
        self.g_queue.set(len(self.queue))
        if self.paged:
            st = self.alloc.stats
            used = self.alloc.num_pages - self.alloc.free_pages
            self.g_occ.set(used / max(self.alloc.num_pages, 1))
            hits = st.get("prefix_hits", 0)
            self.g_hit_ratio.set(
                hits / max(hits + st.get("allocated", 0), 1))
            rev = st.get("revived", 0) - self._revived_seen
            if rev:
                self.c_revived.inc(rev)
                self._revived_seen = st.get("revived", 0)

    def latency_summary(self) -> dict:
        """Exact percentiles over the raw per-request latency lists (the
        serve histograms carry the same data bucketed; this summary is
        what the CLI prints and the energy report embeds)."""
        def pct(vals: list[float]) -> dict:
            if not vals:
                return {"count": 0}
            a = np.asarray(sorted(vals), np.float64)
            return {"count": len(vals),
                    "p50": float(np.percentile(a, 50)),
                    "p95": float(np.percentile(a, 95)),
                    "p99": float(np.percentile(a, 99)),
                    "mean": float(a.mean()), "max": float(a.max())}
        met = sum(1 for ok in self.request_slo_ok.values() if ok)
        total = len(self.request_slo_ok)
        return {"ttft_ms": pct(list(self.request_ttft_ms.values())),
                "tpot_ms": pct(list(self.request_tpot_ms.values())),
                "e2e_ms": pct(list(self.request_e2e_ms.values())),
                "slo": {"target_ms": self.config.latency_slo_ms,
                        "met": met, "violations": total - met,
                        "attainment": met / total if total else None}}

    # ------------------------------------------------------ paged helpers --
    def _attn_share(self) -> float:
        """Effective-occupancy sharing ratio: unique physical pages over
        logical block-table entries -- shared pages are gathered once per
        step, not once per slot (DESIGN.md §11).  1.0 without sharing."""
        if not self.prefix_sharing:
            return 1.0
        logical = int(self.alloc.page_counts().sum())
        if logical == 0:
            return 1.0
        unique = len({pid for s in range(self.slots)
                      for pid in self.alloc.slot_pages(s)})
        return unique / logical

    def _attn_bytes_step(self) -> float:
        """Modeled attention-cache bytes of one decode step, all layers
        (paged: only *allocated* pages move, scaled by the COW sharing
        ratio -- a late-admitted slot's unallocated gap span reads the
        shared zero row and is not billed; contiguous: full strips)."""
        if not self.cfg.has_attention:
            return 0.0
        lengths = None
        spec = self.attn_spec
        if self.paged:
            # express allocated pages as lengths so attn_decode_bytes'
            # ceil(len/page) recovers the exact allocated page count
            lengths = [int(n) * self.page_size
                       for n in self.alloc.page_counts()]
            share = self._attn_share()
            if share != 1.0:
                spec = dataclasses.replace(spec, share=share)
                self.energy.meta["attn_share"] = min(
                    self.energy.meta.get("attn_share", 1.0), share)
                self._observe_share(share)
        return self.cfg.n_layers * attn_decode_bytes(
            spec, slots=self.slots, cache_len=self.cache_len,
            lengths=lengths, n_kv_heads=self.cfg.n_kv_heads,
            d_head=self.cfg.d_head, dtype_bytes=self._cache_dtype_bytes)

    def _sync_tables(self):
        self.state["block_tables"] = jnp.asarray(self.alloc.block_table)

    def _scrub_pages(self, page_ids):
        """Zero the physical rows (all layers) of newly allocated pages
        that were previously freed -- a fresh pool is already zero, so
        only reused pages pay the scrub; eviction itself never copies.
        (COW forks skip this: the fork's device copy overwrites every
        row; adopted prefix pages skip it too: their content IS the
        requested prefix.)"""
        dirty = [pid for pid in page_ids if self.alloc.was_freed(pid)]
        rows = [int(r) for pid in dirty for r in self._perm_np[:, pid]]
        if rows:
            self.c_scrubbed.inc(len(dirty))
            idx = jnp.asarray(rows)
            self.state["k_pages"] = self.state["k_pages"].at[idx].set(0)
            self.state["v_pages"] = self.state["v_pages"].at[idx].set(0)

    def _cow_forks(self) -> bool:
        """Copy-on-write: fork any shared page an active slot is about to
        write this step (refcount > 1 at its write position), device-
        copying the old page's rows into the private copy (DESIGN.md
        §11).  Pool exhaustion during a fork preempts like any other
        allocation; a preemption can also drop the refcount to 1, making
        the fork unnecessary -- hence the re-check."""
        from repro.serve.paged_kv import PoolExhausted
        forked = False
        for s in range(self.slots):
            if not self.active[s]:
                continue
            p = int(self.pos[s])
            while self.alloc.needs_fork(s, p):
                try:
                    old, new = self.alloc.fork(s, p)
                except PoolExhausted:
                    if not self._preempt_victim(s):
                        raise
                    continue
                src = jnp.asarray(self._perm_np[:, old])
                dst = jnp.asarray(self._perm_np[:, new])
                self.state["k_pages"] = self.state["k_pages"].at[dst].set(
                    self.state["k_pages"][src])
                self.state["v_pages"] = self.state["v_pages"].at[dst].set(
                    self.state["v_pages"][src])
                self.c_cow.inc()
                forked = True
                break
        return forked

    def _preempt_victim(self, needer: int) -> bool:
        """Recompute-style preemption under mid-decode pool exhaustion:
        requeue the most recently admitted *other* busy slot (decoding or
        mid-prefill) with its full context as a new prompt (its
        generation budget carries over), release its references, and let
        the needer retry.  Refcounted release means a victim sharing
        prefix pages with a survivor frees only its private tail.  False
        when the needer is the only busy slot (the pool is genuinely too
        small for one sequence -- the caller's error stands)."""
        cands = [s for s in range(self.slots)
                 if s != needer
                 and (self.active[s] or self._prefill_len[s] >= 0)]
        if not cands:
            return False
        victim = max(cands, key=lambda s: self._admit_seq[s])
        req = self.slot_req[victim]
        self.active[victim] = False
        self._prefill_len[victim] = -1
        self._prefill_done[victim] = 0
        self._slot_prompt[victim] = None
        self.alloc.release(victim)
        self._sync_tables()
        self.preemptions += 1
        self.c_preempt.inc()
        self.tracer.instant("serve.preempt", req=req, needer=needer)
        # a victim preempted *past its deadline* must not rejoin the
        # queue to be readmitted and re-prefilled (it can never meet its
        # deadline again) -- finish it with an error instead, freeing
        # its pages for the needer (DESIGN.md §14)
        if self._deadline_expired(req, time.monotonic()):
            self._fault("deadline", req=req)
            self._finish_error(req, "deadline")
        else:
            self.queue.insert(0, (req, list(self.out[req])))
            self._set_phase(req, "queued")
        return True

    # ------------------------------------------------- deadlines / shed --
    def _deadline_expired(self, req_id: int, now: float) -> bool:
        if self.deadline_ms is None:
            return False
        arr = self.arrival_s.get(req_id)
        return arr is not None and (now - arr) * 1e3 > self.deadline_ms

    def _enforce_deadlines(self) -> None:
        """Step watchdog: fail every request past its per-request
        deadline (``ServeConfig.deadline_ms`` on the arrival clock) --
        queued requests drop out of the queue, busy slots are evicted
        via :meth:`_fail_slot`.  Runs at the top of every scheduler
        iteration, so a deadline is enforced within one step."""
        if self.deadline_ms is None:
            return
        now = time.monotonic()
        expired = [(r, p) for r, p in self.queue
                   if self._deadline_expired(r, now)]
        if expired:
            self.queue = [(r, p) for r, p in self.queue
                          if not self._deadline_expired(r, now)]
            for r, _ in expired:
                self._fault("deadline", req=r, where="queued")
                self._finish_error(r, "deadline")
        for s in range(self.slots):
            busy = self.active[s] or self._prefill_len[s] >= 0
            if busy and self._deadline_expired(self.slot_req[s], now):
                self._fault("deadline", req=self.slot_req[s],
                            where="slot")
                self._fail_slot(s, "deadline")

    def _should_shed(self) -> bool:
        """Load-shedding watermark check (DESIGN.md §14): shed the
        queue head when pool occupancy or the observed SLO-violation
        rate crosses its configured watermark."""
        sc = self.config
        if sc.shed_occupancy is not None and self.paged \
                and self.alloc.occupancy() >= sc.shed_occupancy:
            return True
        if sc.shed_violation_rate is not None and self.request_slo_ok:
            viol = sum(1 for ok in self.request_slo_ok.values()
                       if not ok)
            if viol / len(self.request_slo_ok) >= sc.shed_violation_rate:
                return True
        return False

    def _shed_queue(self) -> None:
        while self.queue and self._should_shed():
            req_id, _ = self.queue.pop(0)
            self.c_shed.inc()
            self.tracer.instant("serve.shed", req=req_id)
            self._finish_error(req_id, "shed")

    # -------------------------------------------------------- scheduling --
    def submit(self, req_id: int, prompt: list[int],
               arrival_ts: float | None = None):
        """Queue a request.  ``arrival_ts`` is its arrival on the
        ``time.monotonic`` clock in seconds (default: now) -- TTFT, e2e
        latency and SLO attainment are accounted from it, so a driver
        replaying a recorded arrival trace passes the recorded stamps."""
        t = time.monotonic() if arrival_ts is None else float(arrival_ts)
        self.arrival_s[req_id] = t
        self.queue.append((req_id, list(prompt)))
        self.c_submitted.inc()
        self.tracer.begin_async("request", req_id, ts=t * 1e6,
                                prompt_tokens=len(prompt))
        self._req_phase[req_id] = None
        self.tracer.begin_async("request.queued", req_id, ts=t * 1e6)
        self._req_phase[req_id] = "queued"

    def _admit(self):
        """Lockstep admission: whole-prompt prefill at admission time
        (token-by-token through the decode step -- works for every
        family, including ssm/hybrid)."""
        self._shed_queue()
        for slot in range(self.slots):
            if self.active[slot] or not self.queue:
                continue
            req_id, prompt = self.queue[0]
            if self.paged:
                from repro.serve.paged_kv import pages_needed
                need = pages_needed(len(prompt), self.page_size)
                if need > self.alloc.num_pages:
                    raise RuntimeError(
                        f"prompt of {len(prompt)} tokens exceeds the "
                        f"whole page pool ({self.alloc.num_pages} pages "
                        f"x {self.page_size} tokens)")
                # +1 decode-headroom page (when the pool can ever supply
                # it): an admission that exactly fills the pool would
                # force a preemption on its very first decode step
                want = min(need + 1, self.alloc.num_pages)
                if want > self.alloc.free_pages:
                    # pool pressure: head-of-line blocks until a release
                    # frees pages (admission is bounded by the pool, not
                    # by any per-slot cache_len)
                    break
            self.queue.pop(0)
            self._set_phase(req_id, "prefill")
            if self.paged:
                self._scrub_pages(self.alloc.ensure_range(slot, len(prompt)))
                self._sync_tables()
            # prefill the prompt token-by-token into this slot's cache
            # row, metered as one "prefill" reading whose joules all
            # belong to this request (lockstep prefill is single-request
            # work -- continuous chunks split by tokens instead)
            mask = np.zeros(self.slots, bool)
            mask[slot] = True  # slot-isolated prefill writes
            with self.tracer.span("serve.prefill", req=req_id,
                                  tokens=len(prompt)), \
                    EnergyMeter("prefill", backend=self.power,
                                reporter=self.energy,
                                hints=WorkloadHints(
                                    flops=self._tok_flops * len(prompt),
                                    hbm_bytes=self._gemm_bytes_step
                                    * len(prompt),
                                    gemm_bytes=self._gemm_bytes_step
                                    * len(prompt),
                                    f_scale=self.f_scale)) as em:
                for i, tok in enumerate(prompt):
                    toks = np.zeros((self.slots, 1), np.int32)
                    toks[slot, 0] = tok
                    logits, self.state = self._step(
                        self.params, self.state, jnp.asarray(toks),
                        jnp.asarray(i, jnp.int32), jnp.asarray(mask))
            self.request_joules[req_id] = \
                self.request_joules.get(req_id, 0.0) + em.reading.joules
            self.pos[slot] = len(prompt)
            self.active[slot] = True
            self._set_phase(req_id, "decode")
            self.slot_req[slot] = req_id
            self._slot_prompt[slot] = list(prompt)
            self.out[req_id] = list(prompt)
            self.request_emitted.setdefault(req_id, 0)
            self._admit_seq[slot] = self._admit_counter
            self._admit_counter += 1

    def _clone_source(self, prompt: list[int]) -> int | None:
        """A live, fully-prefilled slot whose admitted prompt equals
        ``prompt`` -- its whole block table (partial tail included) can
        be shared by reference (parallel sampling, DESIGN.md §11)."""
        for s in range(self.slots):
            if self.active[s] and self._slot_prompt[s] == prompt:
                return s
        return None

    def _admit_continuous(self):
        """Continuous admission: claim a slot immediately, share what the
        prefix index already holds, and leave the rest of the prompt to
        the chunked prefill stream."""
        from repro.serve.paged_kv import pages_needed
        self._shed_queue()
        for slot in range(self.slots):
            if not self.queue:
                break
            if self.active[slot] or self._prefill_len[slot] >= 0:
                continue
            req_id, prompt = self.queue[0]
            clone_src = None
            if self.paged:
                need = pages_needed(len(prompt), self.page_size)
                if need > self.alloc.num_pages:
                    raise RuntimeError(
                        f"prompt of {len(prompt)} tokens exceeds the "
                        f"whole page pool ({self.alloc.num_pages} pages "
                        f"x {self.page_size} tokens)")
                if self.prefix_sharing:
                    clone_src = self._clone_source(prompt)
                if clone_src is not None:
                    cost = 0   # every page shared by reference
                else:
                    # fresh pages to draw from the free pools: unmatched
                    # pages plus cached (ref==0) matches, which are
                    # revived *out of* the free pool; live matches are
                    # free to adopt
                    matched = (self.alloc.index.match(
                        prompt, self.page_size)
                        if self.prefix_sharing else [])
                    cost = need - sum(
                        1 for pid in matched
                        if self.alloc.refcount(pid) > 0)
                want = min(cost + 1, self.alloc.num_pages)
                if want > self.alloc.free_pages:
                    break
            self.queue.pop(0)
            self._set_phase(req_id, "prefill")
            self.slot_req[slot] = req_id
            self._slot_prompt[slot] = list(prompt)
            self.out[req_id] = list(prompt)
            self.request_emitted.setdefault(req_id, 0)
            self._admit_seq[slot] = self._admit_counter
            self._admit_counter += 1
            if clone_src is not None:
                # whole-table fork: prompt K/V (and the source's partial
                # tail page) shared by reference, zero prefill compute;
                # the first write into any shared page COW-forks it
                self.alloc.clone_table(clone_src, slot)
                self._sync_tables()
                self.pos[slot] = len(prompt)
                self.active[slot] = True
                self._set_phase(req_id, "decode")
                continue
            adopted = self.alloc.adopt_prefix(slot, prompt) \
                if self.prefix_sharing else 0
            if adopted:
                self._sync_tables()
            if adopted >= len(prompt):
                # page-aligned prompt fully served from the index
                self.pos[slot] = len(prompt)
                self.active[slot] = True
                self._set_phase(req_id, "decode")
            else:
                self._prefill_len[slot] = len(prompt)
                self._prefill_done[slot] = adopted

    def _prefill_step(self) -> int:
        """One chunked-prefill gang under the per-step token budget:
        oldest admissions first, each taking up to the remaining budget.
        Gang shapes are static -- (slots, prefill_budget), short rows
        padded with length 0 -- so the jitted chunk step compiles once."""
        from repro.serve.paged_kv import PoolExhausted
        gang = [s for s in range(self.slots) if self._prefill_len[s] >= 0]
        if not gang:
            return 0
        gang.sort(key=lambda s: self._admit_seq[s])
        budget = self.prefill_budget
        rows: list[tuple[int, int, int]] = []
        for s in gang:
            if budget <= 0:
                break
            take = min(budget, int(self._prefill_len[s]
                                   - self._prefill_done[s]))
            if take <= 0:
                continue
            rows.append((s, int(self._prefill_done[s]), take))
            budget -= take
        if not rows:
            return 0
        if self.paged:
            new: list[int] = []
            for s, done, take in rows:
                while True:
                    try:
                        new += self.alloc.ensure_range(s, done + take)
                        break
                    except PoolExhausted:
                        if not self._preempt_victim(s):
                            raise
            # a preemption may have evicted a later gang member: keep
            # only the rows still mid-prefill
            rows = [(s, d, t) for s, d, t in rows
                    if self._prefill_len[s] >= 0]
            if new:
                self._scrub_pages(new)
            self._sync_tables()
            if not rows:
                return 0
        toks = np.zeros((self.slots, self.prefill_budget), np.int32)
        sl = np.zeros(self.slots, np.int32)
        st = np.zeros(self.slots, np.int32)
        ln = np.zeros(self.slots, np.int32)
        for i, (s, done, take) in enumerate(rows):
            toks[i, :take] = self._slot_prompt[s][done:done + take]
            sl[i] = s
            st[i] = done
            ln[i] = take
        # pad rows (length 0) still need *distinct* slot ids -- the
        # chunk's dense scatter would otherwise collide a pad row with a
        # real row on the same cache strip (prefill_kv_chunk's contract);
        # a length-0 row writes its slot's rows back unchanged
        spare = iter(s for s in range(self.slots)
                     if s not in {r[0] for r in rows})
        for i in range(len(rows), self.slots):
            sl[i] = next(spare)
        total = sum(t for _, _, t in rows)
        with EnergyMeter("prefill-chunk", backend=self.power,
                         reporter=self.energy,
                         hints=WorkloadHints(
                             flops=self._tok_flops * total,
                             hbm_bytes=self._gemm_bytes_step,
                             gemm_bytes=self._gemm_bytes_step,
                             f_scale=self.f_scale)) as em:
            self.state = self._chunk(
                self.params, self.state, jnp.asarray(toks),
                jnp.asarray(sl), jnp.asarray(st), jnp.asarray(ln))
        # per-request attribution weighted by the prompt tokens each row
        # actually processed this chunk -- a gang sharing one reading
        # must not bill a 1-token tail row like a budget-filling row
        for s, done, take in rows:
            r = self.slot_req[s]
            self.request_joules[r] = self.request_joules.get(r, 0.0) \
                + em.reading.joules * take / total
        for s, done, take in rows:
            self._prefill_done[s] = done + take
            if self._prefill_done[s] >= self._prefill_len[s]:
                # prompt fully cached: index its full-page prefix for
                # future admissions, start decoding on the slot's own
                # clock (first decode feeds the prompt's last token at
                # position len, matching the lockstep discipline)
                if self.prefix_sharing:
                    self.alloc.register_prefix(s, self._slot_prompt[s])
                self._prefill_len[s] = -1
                self._prefill_done[s] = 0
                self.pos[s] = len(self._slot_prompt[s])
                self.active[s] = True
                self._set_phase(self.slot_req[s], "decode")
        return total

    def _sample(self, logits_row) -> int:
        if self.temperature <= 0:
            return int(np.argmax(logits_row))
        p = np.exp(logits_row / self.temperature -
                   np.max(logits_row / self.temperature))
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def _decode_once(self, max_new: int):
        """One metered decode step over the live slots: page allocation
        (with preemption on exhaustion), COW forks, the jitted step, and
        sampling/retirement.  Shared by both schedulers; positions are
        the per-slot vector when the family allows it, the historical
        shared scalar (max over live slots) otherwise."""
        from repro.serve.paged_kv import PoolExhausted
        if self.chaos is not None and self.chaos.match(
                "kernel", step=self._iter) is not None:
            # a runtime launch fault surfaces *inside* jit where the
            # dispatch-level hook cannot fire (the trace ran once at
            # compile time) -- injected here, recovered by the retry
            # path engaging the sticky XLA fallback (DESIGN.md §14)
            raise InjectedFault("kernel", f"step={self._iter}")
        scalar_pos = None if self._vector_pos \
            else int(self.pos[self.active].max())
        if self.paged:
            # every live slot needs the page holding its next position;
            # pool exhaustion preempts the youngest other slot instead of
            # killing the loop (extent overflow is deterministic -- never
            # retried)
            new: list[int] = []
            for s in range(self.slots):
                while self.active[s]:
                    try:
                        new += self.alloc.ensure(
                            s, int(self.pos[s]) if self._vector_pos
                            else scalar_pos)
                        break
                    except PoolExhausted:
                        if not self._preempt_victim(s):
                            raise
            forked = self._cow_forks() if self.prefix_sharing else False
            if new:    # steady-state steps re-upload nothing
                self._scrub_pages(new)
            if new or forked:
                self._sync_tables()
        toks = np.zeros((self.slots, 1), np.int32)
        for s in range(self.slots):
            if self.active[s]:
                toks[s, 0] = self.out[self.slot_req[s]][-1]
        n_active = int(self.active.sum())
        attn_bytes = self._attn_bytes_step()
        # report the peak per-step attention traffic (paged bytes
        # grow with occupancy; contiguous is constant)
        self.energy.meta["attn_bytes_step"] = max(
            self.energy.meta["attn_bytes_step"], attn_bytes)
        pos_arg = jnp.asarray(self.pos) if self._vector_pos \
            else jnp.asarray(scalar_pos, jnp.int32)
        with EnergyMeter("decode-step", backend=self.power,
                         reporter=self.energy,
                         hints=WorkloadHints(
                             flops=self._tok_flops * n_active,
                             hbm_bytes=self._gemm_bytes_step
                             + attn_bytes,
                             attn_bytes=attn_bytes,
                             gemm_bytes=self._gemm_bytes_step,
                             f_scale=self.f_scale)) as em:
            logits, self.state = self._step(
                self.params, self.state, jnp.asarray(toks), pos_arg,
                jnp.asarray(self.active))
            logits = np.asarray(logits[:, 0], np.float32)
        # token-weighted attribution degenerates to an even split here:
        # every active slot processed exactly one token this step
        # (prefill readings are weighted by their real token counts)
        j_per_req = em.reading.joules / max(n_active, 1)
        for s in range(self.slots):
            if self.active[s]:
                r = self.slot_req[s]
                self.request_joules[r] = \
                    self.request_joules.get(r, 0.0) + j_per_req
        # NaN/Inf quarantine (DESIGN.md §14): injected poisoning first,
        # then the guard scan.  Only the offending slot's request is
        # failed; co-resident slots sample normally this very step.
        # Quarantine never raises: it runs after every retryable fault
        # point in the iteration, so restore-and-replay cannot revive a
        # request that was failed here.
        if self.chaos is not None:
            for s in range(self.slots):
                if self.active[s] and self.chaos.match(
                        "nan", step=self._iter,
                        request=self.slot_req[s]) is not None:
                    if not logits.flags.writeable:
                        logits = np.array(logits)  # device views are RO
                    logits[s, :] = np.nan
        if self.guards:
            finite = np.isfinite(logits).all(axis=1)
            for s in range(self.slots):
                if self.active[s] and not finite[s]:
                    self._fault("nan", req=self.slot_req[s], slot=s)
                    self._fail_slot(s, "nan")
        t_tok = time.monotonic()
        for s in range(self.slots):
            if not self.active[s]:
                continue
            tok = self._sample(logits[s])
            r = self.slot_req[s]
            self.out[r].append(tok)
            self.request_emitted[r] += 1
            if r not in self.first_token_s:
                self.first_token_s[r] = t_tok   # TTFT numerator
            self.pos[s] = (self.pos[s] + 1) if self._vector_pos \
                else scalar_pos + 1
            if tok == self.eos_id or self.request_emitted[r] >= max_new:
                self.active[s] = False
                self._slot_prompt[s] = None
                self._finish_request(r)
                if self.paged:
                    # copy-free eviction: the slot drops its references;
                    # pages go back on a free pool only at refcount zero
                    # (shared prefix pages survive for their other
                    # mappers / the prefix index)
                    self.alloc.release(s)
                    self._sync_tables()

    # --------------------------------------------- fault-tolerant loop ----
    def _engage_kernel_fallback(self, reason: str) -> None:
        """Graceful degradation under an injected kernel fault
        (DESIGN.md §14): mark this loop's paged-attention shape for the
        sticky XLA reference fallback, then rebuild the jitted wrappers
        so the retrace dispatches through it.  One-way for the loop's
        lifetime; metered on ``serve.degraded``.  A real failure of a
        jitted step is never retried: it propagates."""
        if self._kernel_degraded:
            return
        self._kernel_degraded = True
        if self.paged:
            from repro.kernels import paged_attention as pa
            key = pa.fallback_key(
                self.slots, self.cfg.n_heads, self.cfg.d_head,
                self.page_size, self.alloc.max_pages_per_slot)
            pa.mark_fallback(key, reason=reason)
        self.c_degraded.inc()
        self.tracer.instant("serve.degraded", reason=reason)
        self._build_jits()

    def _pending(self) -> bool:
        if self.mode == "continuous":
            return bool(self.queue or self.active.any()
                        or (self._prefill_len >= 0).any())
        return bool(self.queue or self.active.any())

    def _iteration_body(self, max_new: int) -> None:
        """One scheduler iteration under the ``serve.step`` span.
        Within-iteration fault ordering (DESIGN.md §14): injected
        step/straggler faults first, deadlines next, then admission
        (alloc faults), prefill/decode (kernel faults), and the NaN
        quarantine last -- every retryable point precedes the
        unretryable quarantine, so a restore-and-replay can never
        revive a request the quarantine already failed."""
        tr = self.tracer
        it = self._iter
        if self.chaos is not None:
            ev = self.chaos.match("straggler", step=it)
            if ev is not None:
                # counted at injection: the EMA watchdog needs warmup
                # and cannot be relied on to flag an early delay
                self._fault("straggler", step=it, seconds=ev.seconds)
                time.sleep(ev.seconds)
            self.chaos.check("step", step=it)
        with tr.span("serve.step", mode=self.mode):
            self._enforce_deadlines()
            if self.mode == "continuous":
                with tr.span("serve.admit"):
                    self._admit_continuous()
                with tr.span("serve.prefill_chunk"):
                    n = self._prefill_step()
                self.prefill_tokens_per_step.append(n)
                if n:
                    self.m_prefill_tok.observe(n)
                if self.active.any():
                    with tr.span("serve.decode"):
                        self._decode_once(max_new)
            else:
                with tr.span("serve.admit"):
                    self._admit()
                if self.active.any():
                    with tr.span("serve.decode"):
                        self._decode_once(max_new)

    def _recover(self, e: TransientFault, attempt: int) -> None:
        """Retry path after a transient fault: engage the kernel
        fallback when the fault was an injected kernel fault, rewind to
        the last snapshot (restore-and-replay), back off exponentially."""
        if getattr(e, "point", None) == "kernel":
            self._engage_kernel_fallback(repr(e))
        if self.snapshotter is not None:
            t0 = time.perf_counter()
            with self.tracer.span("serve.restore", attempt=attempt,
                                  error=repr(e)):
                self.snapshotter.restore()
            self.c_restores.inc()
            self.h_restore_ms.observe(
                (time.perf_counter() - t0) * 1e3)
        back = self.config.retry_backoff_s
        if back:
            time.sleep(min(back * 2 ** (attempt - 1), 1.0))

    def _run_iteration(self, max_new: int) -> None:
        if self.snapshotter is not None:
            self.snapshotter.maybe_snapshot(self._iter)
        if self.chaos is not None:
            _chaos.set_context(step=self._iter)
        t0 = time.perf_counter()
        attempt = 0
        while True:
            try:
                self._iteration_body(max_new)
                break
            except TransientFault as e:
                attempt += 1
                point = getattr(e, "point", "step")
                self._fault(point, error=repr(e), attempt=attempt)
                self.c_retries.inc()
                if attempt > self.config.max_step_retries:
                    raise
                self._recover(e, attempt)
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.m_step.observe(dt_ms)
        # EMA step-time watchdog; the first iterations pay jit compile
        # and would poison the EMA, so they are skipped
        if self.guards and self._iter >= 2 \
                and self.straggler.observe(self._iter, dt_ms / 1e3):
            self._fault("straggler_detected", step=self._iter,
                        ms=dt_ms)
        self._pump_gauges()
        self._iter += 1

    def run(self, max_new: int = 32) -> dict[int, list[int]]:
        """Decode until queue + slots drain (or max_new per request,
        tracked per request so a preempted sequence resumes its budget).
        Each scheduler iteration runs under a ``serve.step`` span with
        admit/prefill/decode children, feeds the step-latency histogram
        and refreshes the occupancy gauges (DESIGN.md §12).  Iterations
        run under the fault-tolerance machinery (DESIGN.md §14):
        snapshot on cadence, bounded retry with restore-and-replay on
        :class:`TransientFault`, the chaos injector installed as this
        thread's ambient fault source."""
        with _chaos.install(self.chaos):
            while self._pending():
                self._run_iteration(max_new)
        self.energy.meta["latency"] = self.latency_summary()
        return self.out


def main(argv=None, prompts=None):
    """The serve CLI; returns the drained :class:`ServeLoop`.

    ``prompts`` (token lists) replaces the ``--requests`` random prompts
    of ``--prompt-len`` tokens, for callers that need a particular mix
    of requests, such as prompts that share a prefix.  Exits non-zero
    when the loop degraded to the reference kernel without an injected
    kernel fault."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--layout", default=None,
                    choices=["contiguous", "paged"],
                    help="KV cache layout (DESIGN.md §10); default "
                         "contiguous")
    ap.add_argument("--paged", action="store_true",
                    help="deprecated alias for --layout paged")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (with --layout paged)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size (default: the contiguous "
                         "cache's token footprint)")
    ap.add_argument("--mode", default="lockstep",
                    choices=["lockstep", "continuous"],
                    help="scheduler: lockstep (whole-prompt prefill at "
                         "admission) or continuous batching with chunked "
                         "prefill (DESIGN.md §11)")
    ap.add_argument("--prefill-budget", type=int, default=32,
                    help="max prompt tokens prefilled per decode step "
                         "(with --mode continuous)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable COW prompt-prefix sharing (paged + "
                         "continuous only)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--power-backend", default=None,
                    choices=["rapl", "nvml", "model"],
                    help="pin the energy telemetry backend (default: auto)")
    ap.add_argument("--energy-report", default=None, metavar="PATH",
                    help="write the per-step energy report JSON here")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="time-to-first-token SLO target in ms; per-"
                         "request attainment is accounted and summarised "
                         "(DESIGN.md §12)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the span trace as JSONL here (convert / "
                         "validate with python -m repro.obs.trace, load "
                         "the converted JSON in Perfetto)")
    ap.add_argument("--metrics-report", default=None, metavar="PATH",
                    help="write the metrics registry snapshot JSON here")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the metrics + span layer entirely "
                         "(the near-zero-overhead baseline "
                         "bench_obs_overhead measures against)")
    ap.add_argument("--objective", default=None,
                    choices=["time", "energy", "edp"],
                    help="route every GEMM through the autotuner "
                         "adjudicated on this metric (DESIGN.md §8); "
                         "default keeps the XLA engine")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline on the arrival clock; "
                         "expired requests finish with an error "
                         "(DESIGN.md §14)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault-injection schedule, e.g. "
                         "'alloc@step=2,nan@step=3:req=1,"
                         "straggler@step=4:delay=0.3' (DESIGN.md §14)")
    ap.add_argument("--snapshot-every", type=int, default=None,
                    help="serve-state snapshot cadence in scheduler "
                         "iterations (default: 1 under --chaos, else "
                         "off)")
    ap.add_argument("--snapshot-dir", default=None, metavar="PATH",
                    help="also persist snapshots to disk through the "
                         "checkpoint store (default: in-memory only)")
    ap.add_argument("--shed-occupancy", type=float, default=None,
                    help="shed queued requests when page-pool occupancy "
                         "crosses this watermark (0..1]")
    ap.add_argument("--shed-violation-rate", type=float, default=None,
                    help="shed queued requests when the observed SLO-"
                         "violation rate crosses this watermark (0..1]")
    ap.add_argument("--max-step-retries", type=int, default=2,
                    help="bounded retries per scheduler iteration on a "
                         "transient fault")
    ap.add_argument("--no-fault-guards", action="store_true",
                    help="disable the NaN quarantine + launch-fault "
                         "classification (the guards-off baseline "
                         "bench_fault_tolerance measures against)")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no serving loop")
    enable_compile_cache()
    layout = args.layout or ("paged" if args.paged else "contiguous")
    serve_cfg = ServeConfig(
        slots=args.slots, cache_len=args.cache_len,
        temperature=args.temperature, seed=args.seed,
        objective=args.objective, layout=layout,
        page_size=args.page_size, num_pages=args.num_pages,
        mode=args.mode, prefill_budget=args.prefill_budget,
        prefix_sharing=not args.no_prefix_sharing,
        latency_slo_ms=args.slo_ms, obs=not args.no_obs,
        fault_guards=not args.no_fault_guards,
        deadline_ms=args.deadline_ms,
        max_step_retries=args.max_step_retries,
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir,
        shed_occupancy=args.shed_occupancy,
        shed_violation_rate=args.shed_violation_rate,
        chaos=args.chaos)
    tracer = None
    if args.trace and not args.no_obs:
        from repro.obs import set_default_tracer
        # installed as the process default so spans opened below the
        # loop (tuner resolution, energy attribution) land in it too
        tracer = Tracer(enabled=True)
        set_default_tracer(tracer)
    params = init_model(cfg, jax.random.PRNGKey(args.seed))
    loop = ServeLoop(cfg, params, serve_cfg,
                     power_backend=detect_backend(args.power_backend),
                     tracer=tracer)
    if prompts is None:
        rng = np.random.default_rng(args.seed)
        prompts = [rng.integers(2, cfg.vocab, size=args.prompt_len).tolist()
                   for _ in range(args.requests)]
    for r, prompt in enumerate(prompts):
        loop.submit(r, prompt)
    t0 = time.time()
    out = loop.run(max_new=args.max_new)
    dt = time.time() - t0
    total_new = sum(len(v) - len(prompts[r]) for r, v in out.items())
    totals = loop.energy.totals()
    print(f"[serve] {len(prompts)} requests ({serve_cfg.mode}), "
          f"{total_new} tokens in "
          f"{dt:.2f}s ({total_new / max(dt, 1e-9):.1f} tok/s)")
    n_steps = max(len(loop.energy.readings), 1)
    fs = loop.f_scales
    print(f"[serve] energy ({loop.power.name}, objective={loop.objective}, "
          f"f_scale proj {fs['proj']:g} / mlp {fs['mlp']:g} / "
          f"attn {fs['attn']:g}): {totals['joules']:.2f} J, "
          f"{totals['joules'] / max(total_new, 1):.3f} J/token, "
          f"{totals['joules'] * totals['seconds'] / n_steps ** 2:.3e} "
          f"Js EDP/step")
    print(f"[serve] attention cache ({loop.attn_spec.tag()}): "
          f"~{loop.energy.meta['attn_bytes_step'] / 1e6:.2f} MB/step KV "
          f"traffic next to ~{loop.energy.meta['gemm_bytes_step'] / 1e6:.2f}"
          f" MB/step GEMM weights (modeled)")
    if loop.paged:
        print(f"[serve] page pool: {loop.alloc.num_pages} pages x "
              f"{loop.page_size} tokens, peak stats {loop.alloc.stats}")
    if loop.mode == "continuous":
        peak_prefill = max(loop.prefill_tokens_per_step, default=0)
        print(f"[serve] continuous batching: prefill budget "
              f"{loop.prefill_budget} tok/step (peak used {peak_prefill}), "
              f"{loop.preemptions} preemptions"
              + (f", prefix sharing: {loop.alloc.stats['prefix_hits']} "
                 f"page hits, {loop.alloc.stats['cow_forks']} COW forks, "
                 f"min share {loop.energy.meta['attn_share']:.2f}"
                 if loop.prefix_sharing else ""))
    print(f"[serve] fused epilogues (DESIGN.md §9): "
          f"~{loop.ep_saved_step / 1e6:.2f} MB/step HBM traffic "
          f"eliminated across {loop.slots} slots (modeled)")
    if loop._kernel_degraded:
        print("[serve] kernel degraded to XLA fallback")
    if args.chaos or loop.errors or loop.snapshotter is not None:
        snaps = loop.snapshotter.snapshots if loop.snapshotter else 0
        rests = loop.snapshotter.restores if loop.snapshotter else 0
        print(f"[serve] fault tolerance (DESIGN.md §14): "
              f"{snaps} snapshots, {rests} restores, "
              f"{len(loop.errors)} failed requests")
        for r, reason in sorted(loop.errors.items()):
            print(f"  req {r}: failed ({reason})")
        if loop.chaos is not None:
            print(f"[serve] chaos: {len(loop.chaos.fired)} injected "
                  f"faults {loop.chaos.fired}, schedule "
                  f"{'exhausted' if loop.chaos.exhausted() else 'open'}")
    for r, toks in sorted(out.items()):
        n = len(prompts[r])
        print(f"  req {r}: {toks[:n]} -> {toks[n:][:8]}... "
              f"({loop.request_joules.get(r, 0.0):.2f} J)")
    lat = loop.energy.meta.get("latency") or {}
    ttft, tpot = lat.get("ttft_ms", {}), lat.get("tpot_ms", {})
    if ttft.get("count"):
        print(f"[serve] latency: TTFT p50 {ttft['p50']:.1f} / "
              f"p95 {ttft['p95']:.1f} / p99 {ttft['p99']:.1f} ms"
              + (f", TPOT p50 {tpot['p50']:.2f} / p95 {tpot['p95']:.2f} "
                 f"/ p99 {tpot['p99']:.2f} ms/token"
                 if tpot.get("count") else ""))
    slo = lat.get("slo", {})
    if slo.get("target_ms") is not None:
        n = slo["met"] + slo["violations"]
        print(f"[serve] SLO (TTFT <= {slo['target_ms']:g} ms): "
              f"{slo['met']}/{n} met "
              f"({(slo['attainment'] or 0.0) * 100:.0f}% attainment), "
              f"{slo['violations']} violations")
    if args.energy_report:
        loop.energy.write(args.energy_report)
        print(f"[serve] wrote energy report to {args.energy_report}")
    if args.metrics_report:
        loop.metrics.write(args.metrics_report)
        print(f"[serve] wrote metrics snapshot to {args.metrics_report}")
    if args.trace and tracer is not None:
        tracer.write_jsonl(args.trace)
        print(f"[serve] wrote {len(tracer.events)} trace events to "
              f"{args.trace} (python -m repro.obs.trace {args.trace} "
              f"-o trace.json for Perfetto)")
    injected = loop.chaos is not None and any(
        point == "kernel" for point, *_ in loop.chaos.fired)
    if loop._kernel_degraded and not injected:
        raise SystemExit("[serve] the paged-attention kernel degraded "
                         "without an injected kernel fault")
    return loop


if __name__ == "__main__":
    main()
