"""Paged KV cache (DESIGN.md §10): allocator, Morton page layout, paged
decode-attention kernel vs its XLA reference, paged-vs-contiguous decode
parity, bulk prefill, and the attention-traffic cost model.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.ref import paged_decode_attention_ref
from repro.models import decode_step, init_decode_state, init_model, \
    prefill_kv
from repro.serve.paged_kv import PageAllocator, init_paged_serving, \
    page_permutation, physical_rows
from repro.tune import AttnSpec, attn_decode_bytes
from repro.tune.cache import TuneCache, cache_key

from _hyp import given, settings, st


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen3_1_7b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_model(cfg, jax.random.PRNGKey(0))


# ----------------------------------------------------------- allocator -----
def test_page_permutation_is_a_morton_bijection():
    L, P = 4, 16
    perm = page_permutation(L, P)
    assert perm.shape == (L, P)
    assert sorted(perm.ravel().tolist()) == list(range(L * P))
    # the locality claim: same-page neighbours across layers sit closer
    # in physical rows than the row-major layout's full-P stride
    morton_stride = np.abs(perm[1:] - perm[:-1]).mean()
    assert morton_stride < P, (morton_stride, P)


def test_allocator_lifo_reuse_and_stats():
    a = PageAllocator(num_pages=6, page_size=4, slots=2)
    got = a.ensure_range(0, 10)           # 3 pages
    assert len(got) == 3 and a.pages_in_use == 3
    assert a.seq_lens[0] == 10
    assert a.ensure(0, 10) == []          # page 2 already covers pos 10
    new = a.ensure(0, 12)                 # 4th page
    assert len(new) == 1 and not a.was_freed(new[0])
    freed = a.release(0)
    assert sorted(freed) == sorted(got + new)
    assert a.pages_in_use == 0 and a.seq_lens[0] == 0
    # LIFO: the next admission is served from the just-freed pages
    re = a.ensure_range(1, 4)
    assert re[0] in freed and a.was_freed(re[0])
    assert a.stats["reused"] == 1
    assert a.occupancy() == pytest.approx(1 / 6)


def test_allocator_exhaustion_and_admission():
    a = PageAllocator(num_pages=2, page_size=4, slots=2)
    assert a.can_admit(8) and not a.can_admit(9)
    a.ensure_range(0, 8)
    with pytest.raises(RuntimeError, match="pool exhausted"):
        a.ensure(1, 0)
    b = PageAllocator(num_pages=8, page_size=4, slots=1,
                      max_pages_per_slot=2)
    b.ensure_range(0, 8)
    with pytest.raises(RuntimeError, match="outgrew"):
        b.ensure(0, 8)


def test_init_paged_serving_sizes_agree(cfg):
    """Pool size and block-table width must match between the allocator
    and the device state (a mismatch lets logical ids clamp-alias past
    page_perm), and the default table width is the cache_len equivalent
    plus one page -- not the whole pool (gather span stays
    occupancy-proportional, DESIGN.md §10)."""
    alloc, st = init_paged_serving(cfg, 4, 64, page_size=8)
    assert st["page_perm"].shape == (cfg.n_layers, alloc.num_pages)
    assert st["block_tables"].shape == (4, alloc.max_pages_per_slot)
    assert alloc.max_pages_per_slot == 64 // 8 + 1     # not num_pages=32
    assert st["k_pages"].shape[0] == cfg.n_layers * alloc.num_pages + 1
    # a tiny explicit pool caps the width at the pool
    alloc2, st2 = init_paged_serving(cfg, 2, 64, page_size=8, num_pages=3)
    assert alloc2.max_pages_per_slot == 3
    assert st2["block_tables"].shape == (2, 3)


def test_physical_rows_both_orientations():
    perm = page_permutation(3, 8)
    zero = 3 * 8
    bt = np.asarray([[2, 5, -1], [0, -1, -1]], np.int32)  # (B, maxp)
    rows = np.asarray(physical_rows(perm[1], bt, zero))
    assert rows[0, 0] == perm[1, 2] and rows[0, 2] == zero
    assert rows[1, 1] == zero
    bt_row = np.asarray([4, -1], np.int32)                # (npg,)
    rows2 = np.asarray(physical_rows(perm, bt_row, zero))  # (L, npg)
    assert rows2.shape == (3, 2)
    assert (rows2[:, 0] == perm[:, 4]).all() and (rows2[:, 1] == zero).all()


def test_paged_state_rejects_ssm_and_swa(cfg):
    from repro.configs import get_smoke_config as smoke
    with pytest.raises(ValueError, match="pure-attention"):
        init_decode_state(smoke("mamba2_780m"), 2, 32, paged=True)
    import dataclasses
    swa = dataclasses.replace(cfg, swa_window=16)
    with pytest.raises(ValueError, match="SWA"):
        init_decode_state(swa, 2, 32, paged=True)


# ------------------------------------------------------- kernel vs ref -----
def test_paged_kernel_matches_ref_interpret():
    rng = np.random.default_rng(0)
    B, H, hkv, dh, ps, maxp = 3, 4, 2, 16, 8, 4
    rows = 12 + 1                         # + reserved zero row
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((rows, ps, hkv, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((rows, ps, hkv, dh)), jnp.float32)
    kp = kp.at[-1].set(0)
    vp = vp.at[-1].set(0)
    tab = jnp.asarray(rng.integers(0, rows - 1, size=(B, maxp)), jnp.int32)
    tab = tab.at[1, 2:].set(rows - 1)     # unallocated tail -> zero row
    for pos in (0, 5, 8, 13, 31):
        ref = paged_decode_attention_ref(q, kp, vp, tab, jnp.int32(pos))
        ker = paged_decode_attention_pallas(q, kp, vp, tab,
                                            jnp.int32(pos), interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=0, atol=1e-6)


def test_paged_kernel_zero_page_matches_contiguous_zero_rows():
    """A block table full of zero-row entries must behave exactly like a
    contiguous cache of zero K/V rows (parity of the gap-position
    semantics)."""
    rng = np.random.default_rng(1)
    B, H, hkv, dh, ps = 2, 4, 2, 8, 4
    rows = 4 + 1
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = jnp.zeros((rows, ps, hkv, dh), jnp.float32)
    vp = jnp.zeros_like(kp)
    tab = jnp.full((B, 3), rows - 1, jnp.int32)
    out = paged_decode_attention_ref(q, kp, vp, tab, jnp.int32(5))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=0)


# ------------------------------------------------- decode-step parity ------
import functools


@functools.partial(jax.jit, static_argnames=("cfg",))
def _step_jit(params, cfg, state, toks, pos, mask):
    # module-level jit: traces are shared across tests and hypothesis
    # examples with the same (batch, layout) signature
    return decode_step(params, cfg, state, toks, pos, row_mask=mask)


def _run_both(cfg, params, prompts, steps, page_size, cache_len=64,
              masks=None):
    """Drive paged + contiguous decode_step with an identical schedule;
    returns per-step (contiguous logits, paged logits) pairs."""
    B = len(prompts)
    st_c = init_decode_state(cfg, B, cache_len)
    # allocator + state from the one constructor: pool and block-table
    # width must agree or logical ids alias past page_perm
    alloc, st_p = init_paged_serving(cfg, B, cache_len,
                                     page_size=page_size)
    for s, pr in enumerate(prompts):      # slot-isolated prefill
        mask = np.zeros(B, bool)
        mask[s] = True
        for i, tok in enumerate(pr):
            alloc.ensure(s, i)
            st_p["block_tables"] = jnp.asarray(alloc.block_table)
            toks = np.zeros((B, 1), np.int32)
            toks[s, 0] = tok
            _, st_c = _step_jit(params, cfg, st_c, jnp.asarray(toks),
                                jnp.asarray(i, jnp.int32),
                                jnp.asarray(mask))
            _, st_p = _step_jit(params, cfg, st_p, jnp.asarray(toks),
                                jnp.asarray(i, jnp.int32),
                                jnp.asarray(mask))
    pos = max(len(p) for p in prompts)
    toks = np.asarray([[p[-1]] for p in prompts], np.int32)
    outs = []
    for step in range(steps):
        mask = np.ones(B, bool) if masks is None else np.asarray(masks[step])
        for s in range(B):
            if mask[s]:
                alloc.ensure(s, pos)
        st_p["block_tables"] = jnp.asarray(alloc.block_table)
        lc, st_c = _step_jit(params, cfg, st_c, jnp.asarray(toks),
                             jnp.asarray(pos, jnp.int32),
                             jnp.asarray(mask))
        lp, st_p = _step_jit(params, cfg, st_p, jnp.asarray(toks),
                             jnp.asarray(pos, jnp.int32),
                             jnp.asarray(mask))
        outs.append((np.asarray(lc), np.asarray(lp), mask))
        nxt = np.argmax(np.asarray(lc)[:, 0], -1).astype(np.int32)
        toks = np.where(mask, nxt, toks[:, 0])[:, None].astype(np.int32)
        pos += 1
    return outs


def test_paged_decode_matches_contiguous_fixed(cfg, params):
    """Tier-1 parity smoke: ragged prompts, page size not dividing the
    lengths, identical logits and greedy tokens."""
    outs = _run_both(cfg, params, [[5, 6, 7, 8, 9], [3, 4, 5]],
                     steps=3, page_size=4)
    for lc, lp, mask in outs:
        # the two layouts reduce attention over different extents (page
        # span vs cache_len), so XLA may order the f32 sums differently:
        # a few f32 ulps of logits of size ~1
        np.testing.assert_allclose(lp, lc, rtol=1e-6, atol=1e-5)
        assert (np.argmax(lc[:, 0], -1) == np.argmax(lp[:, 0], -1)).all()


@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(
    page_size=st.sampled_from([4, 8, 16]),
    n_slots=st.integers(min_value=1, max_value=3),
    data=st.data(),
)
def test_paged_decode_matches_contiguous_property(page_size, n_slots, data):
    """Hypothesis property (satellite 1): paged decode_step ==
    contiguous decode_step -- logits and greedy tokens -- across page
    sizes {4, 8, 16}, slot counts, ragged active sets, and prefill
    lengths that don't divide page_size."""
    cfg = get_smoke_config("qwen3_1_7b")
    params = init_model(cfg, jax.random.PRNGKey(0))
    prompts = [
        data.draw(st.lists(st.integers(min_value=2, max_value=100),
                           min_size=1, max_size=13), label=f"prompt{s}")
        for s in range(n_slots)
    ]
    steps = data.draw(st.integers(min_value=1, max_value=2), label="steps")
    masks = []
    for i in range(steps):
        m = [data.draw(st.booleans(), label=f"m{i}{s}")
             for s in range(n_slots)]
        if not any(m):
            m[0] = True                  # at least one live slot per step
        masks.append(m)
    outs = _run_both(cfg, params, prompts, steps, page_size, masks=masks)
    for lc, lp, mask in outs:
        np.testing.assert_allclose(lp, lc, rtol=1e-5, atol=1e-5)
        live = np.nonzero(mask)[0]
        assert (np.argmax(lc[live, 0], -1) == np.argmax(lp[live, 0],
                                                        -1)).all()


# -------------------------------------------------------- bulk prefill -----
def test_bulk_prefill_matches_stepwise_both_layouts(cfg, params):
    prompt = [5, 6, 7, 8, 9]              # 5 tokens, page_size 4: ragged
    B, C, ps = 2, 32, 4
    # stepwise reference (the ServeLoop admission path)
    st_c = init_decode_state(cfg, B, C)
    mask = np.asarray([True, False])
    for i, tok in enumerate(prompt):
        toks = np.asarray([[tok], [0]], np.int32)
        _, st_c = decode_step(params, cfg, st_c, jnp.asarray(toks),
                              jnp.asarray(i, jnp.int32),
                              row_mask=jnp.asarray(mask))
    # bulk contiguous
    st_b = init_decode_state(cfg, B, C)
    logits, st_b = prefill_kv(params, cfg, st_b, prompt, slot=0)
    assert logits.shape[1] == len(prompt)
    np.testing.assert_allclose(
        np.asarray(st_b["k"][:, 0, :5]), np.asarray(st_c["k"][:, 0, :5]),
        rtol=1e-5, atol=1e-5)
    # bulk paged: same K/V land in the slot's pages
    alloc = PageAllocator(num_pages=8, page_size=ps, slots=B)
    st_p = init_decode_state(cfg, B, C, paged=True, page_size=ps,
                             num_pages=8)
    alloc.ensure_range(0, len(prompt))
    st_p["block_tables"] = jnp.asarray(alloc.block_table)
    _, st_p = prefill_kv(params, cfg, st_p, prompt, slot=0)
    perm = np.asarray(st_p["page_perm"])
    for layer in range(cfg.n_layers):
        got = np.concatenate([
            np.asarray(st_p["k_pages"][perm[layer, pid]])
            for pid in alloc.slot_pages(0)], axis=0)[:len(prompt)]
        np.testing.assert_allclose(
            got, np.asarray(st_b["k"][layer, 0, :len(prompt)]),
            rtol=1e-5, atol=1e-5)
    # zero row untouched
    assert float(jnp.abs(st_p["k_pages"][-1]).max()) == 0.0


# ----------------------------------------------------------- cost model ----
def test_paged_bytes_strictly_below_contiguous_at_half_occupancy():
    """Acceptance: paged predicted bytes < contiguous at <= 50% slot
    occupancy (the over-allocation the strip cache pays by design)."""
    slots, C, ps = 8, 128, 8
    kw = dict(slots=slots, cache_len=C, n_kv_heads=2, d_head=32,
              dtype_bytes=4)
    contig = attn_decode_bytes(AttnSpec("contig"), **kw)
    for occ in (0.125, 0.25, 0.5):
        active = max(1, int(slots * occ))
        lens = [int(C * occ)] * active + [0] * (slots - active)
        paged = attn_decode_bytes(AttnSpec("paged", ps), lengths=lens, **kw)
        assert paged < contig, (occ, paged, contig)
    # full occupancy: the strip is optimal, paged pays the table reads
    full = attn_decode_bytes(AttnSpec("paged", ps),
                             lengths=[C] * slots, **kw)
    assert full == pytest.approx(contig + 4.0 * slots * (C // ps))


def test_attn_spec_validation_and_tags():
    assert AttnSpec("contig").tag() == "contig"
    assert AttnSpec("paged", 8).tag() == "paged-p8"
    with pytest.raises(ValueError):
        AttnSpec("ring")
    with pytest.raises(ValueError):
        AttnSpec("paged")                 # page_size required


def test_attn_keyspace_isolated_from_gemm_and_per_layout(tmp_path,
                                                         monkeypatch):
    """Acceptance: the paged kernel tunes under its own cache keyspace
    (.../attn=paged-p8), disjoint from the GEMM keys and from the
    contiguous layout's keys."""
    from repro.tune import autotune_attn

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    cache = TuneCache(str(tmp_path / "t.json"))
    kw = dict(n_heads=4, n_kv_heads=2, d_head=32, cache=cache,
              objective="energy")
    rp = autotune_attn(8, 128, attn=AttnSpec("paged", 8), **kw)
    rc = autotune_attn(8, 128, attn=AttnSpec("contig"), **kw)
    assert rp.key.endswith("/attn=paged-p8")
    assert rc.key.endswith("/attn=contig")
    assert rp.key.startswith("attn/") and rp.key != rc.key
    gemm_key = cache_key(8, 64, 128, "float32", "cpu", objective="energy")
    assert gemm_key not in (rp.key, rc.key)
    assert cache.get(rp.key)["attn"] == "paged-p8"
    # cache hit round-trip
    again = autotune_attn(8, 128, attn=AttnSpec("paged", 8), **kw)
    assert again.from_cache and again.config == rp.config


def test_attn_and_mlp_shapes_resolve_different_f_scale(tmp_path,
                                                       monkeypatch):
    """Satellite: the memory-bound decode-attention gather and a
    compute-bound MLP projection tune to different DVFS points under the
    energy objective -- the per-shape split the telemetry stamps."""
    from repro.tune import resolved_attn_f_scale, resolved_f_scale

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    cache = TuneCache(str(tmp_path / "t.json"))
    f_attn = resolved_attn_f_scale(
        8, 4096, n_heads=16, n_kv_heads=8, d_head=128,
        attn=AttnSpec("paged", 8), cache=cache, objective="energy")
    f_mlp = resolved_f_scale(2048, 2048, 2048, cache=cache,
                             objective="energy")
    assert f_attn < f_mlp, (f_attn, f_mlp)


# ---------------------------------------------- corruption detection ------
def test_invariants_name_negative_refcount():
    """check_invariants must fail loudly (RuntimeError naming the page)
    on a corrupted allocator, not serve another request's KV rows."""
    a = PageAllocator(num_pages=4, page_size=4, slots=2)
    (pid,) = a.ensure_range(0, 4)
    a.check_invariants()                      # clean state passes
    a.ref[pid] = -2                           # simulated corruption
    with pytest.raises(RuntimeError, match=f"page {pid}: negative"):
        a.check_invariants()


def test_invariants_name_orphaned_page():
    a = PageAllocator(num_pages=4, page_size=4, slots=2)
    a.ensure_range(0, 4)
    pid = a._free[-1]                         # a free page...
    a._free.remove(pid)                       # ...leaks out of the pool
    with pytest.raises(RuntimeError, match=f"page {pid}: orphaned"):
        a.check_invariants()


def test_invariants_name_double_freed_page():
    """A forged second release of the same page (the classic
    use-after-free precursor) lands it on the free list twice."""
    a = PageAllocator(num_pages=4, page_size=4, slots=2)
    (pid,) = a.ensure_range(0, 4)
    a.release(0)
    # forge the state release() just cleared, then release again
    a.block_table[0, 0] = pid
    a.ref[pid] = 1
    a.seq_lens[0] = 4
    a.release(0)
    with pytest.raises(RuntimeError, match=f"page {pid}: double-free"):
        a.check_invariants()


def test_invariants_name_free_but_still_mapped_page():
    a = PageAllocator(num_pages=4, page_size=4, slots=2)
    (pid,) = a.ensure_range(0, 4)
    a._free.append(pid)                       # freed while still mapped
    with pytest.raises(RuntimeError,
                       match=f"page {pid}: on a free pool"):
        a.check_invariants()


def test_invariants_catch_evicted_cached_page():
    """PrefixIndex.evict of a page still parked on the cached-free list
    strands it: unreachable for prefix reuse, yet never scrubbed back to
    the plain pool.  The audit must name it."""
    a = PageAllocator(num_pages=4, page_size=4, slots=2,
                      prefix_sharing=True)
    a.ensure_range(0, 8)
    a.register_prefix(0, list(range(8)))
    a.release(0)
    assert a._free_cached and a.check_invariants() is None
    pid = a._free_cached[0]
    a.index.evict(pid)                        # out-of-band eviction
    with pytest.raises(RuntimeError,
                       match=f"page {pid}: on the cached-free list"):
        a.check_invariants()
