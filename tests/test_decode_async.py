"""Overlapped decode data plane (ISSUE 20): the async double-buffered
tick pipeline — device-resident token/position chains consumed at
depth-1 lag — must be BITWISE the greedy oracle across the whole
scheduling matrix (mixed lengths, continuous arrival, preemption,
budget stops, spec compose), with ``PADDLE_ASYNC_DECODE=0`` as the
bitwise sync escape; and the host-RAM KV offload tier — park the
coldest session d2h instead of preempt-requeuing, resume via staged
h2d restore — must be invisible in the tokens."""
import numpy as np
import pytest

from paddle_tpu.inference.decode import (DecodeEngine, DecodeModelConfig,
                                         NgramProposer, PageTableManager,
                                         init_decode_params,
                                         reference_generate)
from paddle_tpu.inference.decode.kv_cache import HostKVPool
from paddle_tpu.inference.serving import KVRestoreError

CFG = DecodeModelConfig(vocab_size=32, n_layers=2, n_heads=2, head_dim=8,
                        ffn_dim=32, max_context=64)


def _drive(eng, max_ticks=800):
    for _ in range(max_ticks):
        if not eng.sched.pending():
            return
        eng.run_once()
    raise AssertionError("engine did not drain the workload")


def _engine(monkeypatch=None, async_on=True, **kw):
    if monkeypatch is not None:
        monkeypatch.setenv("PADDLE_ASYNC_DECODE", "1" if async_on else "0")
    kw.setdefault("max_batch", 3)
    kw.setdefault("n_pages", 32)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 8)
    eng = DecodeEngine(CFG, seed=3, **kw)
    eng.warm()
    return eng


@pytest.fixture(scope="module")
def ref_params():
    return init_decode_params(CFG, 3)


# ---------------------------------------------------------------------------
# mode gating
# ---------------------------------------------------------------------------
def test_async_mode_gating(monkeypatch):
    geo = dict(page_size=8, max_pages_per_seq=8)
    monkeypatch.delenv("PADDLE_ASYNC_DECODE", raising=False)
    assert DecodeEngine(CFG, seed=3, **geo)._async_decode is True
    monkeypatch.setenv("PADDLE_ASYNC_DECODE", "0")
    assert DecodeEngine(CFG, seed=3, **geo)._async_decode is False
    # sampling engines keep the synchronous tick: the host Gumbel
    # noise feed makes every tick a host round-trip anyway
    monkeypatch.delenv("PADDLE_ASYNC_DECODE", raising=False)
    assert DecodeEngine(CFG, seed=3, temperature=0.7,
                        **geo)._async_decode is False


# ---------------------------------------------------------------------------
# parity matrix: async vs the dense greedy oracle and the sync twin
# ---------------------------------------------------------------------------
def test_async_mixed_lengths_bitwise_oracle(monkeypatch, ref_params):
    eng = _engine(monkeypatch)
    assert eng._async_decode
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12]]
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    _drive(eng)
    assert [h.result(timeout=5) for h in handles] == \
        [reference_generate(CFG, ref_params, p, 6) for p in prompts]
    # the pipeline really ran lagged: phase accounting published the
    # overlap gauge and the lagged tick was fully consumed
    assert eng._inflight is None
    assert 0.0 < eng.counters["decode_overlap_frac"] <= 1.0


def test_async_escape_env_is_bitwise(monkeypatch):
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12]]
    outs = {}
    for mode in (True, False):
        eng = _engine(monkeypatch, async_on=mode)
        hs = [eng.submit(p, max_new_tokens=7) for p in prompts]
        _drive(eng)
        outs[mode] = [h.result(timeout=5) for h in hs]
    assert outs[True] == outs[False]


def test_async_continuous_arrival_joins_running_batch(monkeypatch,
                                                      ref_params):
    eng = _engine(monkeypatch)
    h1 = eng.submit([7, 3, 1, 2], max_new_tokens=10)
    for _ in range(4):
        eng.run_once()
    assert not h1.done()
    h2 = eng.submit([9, 8], max_new_tokens=5)
    _drive(eng)
    assert h1.result(timeout=5) == reference_generate(
        CFG, ref_params, [7, 3, 1, 2], 10)
    assert h2.result(timeout=5) == reference_generate(
        CFG, ref_params, [9, 8], 5)


def test_async_budget_stop_discards_speculative_extra(monkeypatch,
                                                      ref_params):
    """The depth-1 lag always has one more tick in flight when a
    budget stop lands; the harvest discards that token — outputs are
    EXACTLY max_new_tokens long, never one over."""
    eng = _engine(monkeypatch)
    for n in (1, 2, 3, 5):
        h = eng.submit([5, 4, 3], max_new_tokens=n)
        _drive(eng)
        out = h.result(timeout=5)
        assert len(out) == n
        assert out == reference_generate(CFG, ref_params, [5, 4, 3], n)
    assert eng._inflight is None


def test_async_preemption_under_pool_pressure(monkeypatch):
    """No host tier: pool pressure preempt-requeues mid-pipeline (the
    in-flight tick drains first) and outputs stay the oracle's."""
    monkeypatch.setenv("PADDLE_ASYNC_DECODE", "1")
    cfg = DecodeModelConfig(vocab_size=32, n_layers=1, n_heads=2,
                            head_dim=8, ffn_dim=16, max_context=24)
    eng = DecodeEngine(cfg, seed=7, max_batch=2, n_pages=8, page_size=4,
                       max_pages_per_seq=6)
    eng.warm()
    prompts = [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10, 11]]
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _drive(eng)
    params = init_decode_params(cfg, 7)
    assert [h.result(timeout=5) for h in hs] == \
        [reference_generate(cfg, params, p, 10) for p in prompts]
    assert eng.pool.pages_in_use == 0


def test_async_spec_compose_parity(monkeypatch, ref_params):
    """spec_k engines keep their own verify tick; with async decode on
    for the dense legs the composition stays exact."""
    monkeypatch.setenv("PADDLE_ASYNC_DECODE", "1")
    eng = _engine(monkeypatch, spec_k=3, proposer=NgramProposer())
    loop_prompt = [1, 2, 3, 1, 2, 3, 1, 2]
    h = eng.submit(loop_prompt, max_new_tokens=10)
    _drive(eng)
    assert h.result(timeout=5) == reference_generate(
        CFG, ref_params, loop_prompt, 10)


# ---------------------------------------------------------------------------
# steady-state device-resident ticks
# ---------------------------------------------------------------------------
def test_mutation_epoch_bumped_by_every_mutator():
    pool = PageTableManager(n_pages=8, page_size=4, max_pages_per_seq=4)
    m0 = pool.mutations
    pool.alloc_seq(1, 6)
    assert pool.mutations > m0
    m1 = pool.mutations
    assert pool.append_token(1, 7) is None     # within tail page
    assert pool.mutations == m1                # no table change: no bump
    assert pool.append_token(1, 9) not in (None, -1)   # page boundary
    assert pool.mutations > m1
    m2 = pool.mutations
    pool.free_seq(1)
    assert pool.mutations > m2


def test_async_page_boundary_growth_stays_exact(monkeypatch, ref_params):
    """Generations that cross page boundaries mid-stream invalidate
    the steady signature (the table mutates) and must re-upload
    control vectors without dropping exactness."""
    eng = _engine(monkeypatch, page_size=4, n_pages=32,
                  max_pages_per_seq=8)
    m0 = eng.pool.mutations
    h = eng.submit([1, 2, 3], max_new_tokens=12)   # 3+12 spans 4 pages
    _drive(eng)
    assert h.result(timeout=5) == reference_generate(
        CFG, ref_params, [1, 2, 3], 12)
    assert eng.pool.mutations > m0


# ---------------------------------------------------------------------------
# host-RAM KV offload tier
# ---------------------------------------------------------------------------
def test_host_kv_pool_roundtrip_and_capacity():
    host = HostKVPool(n_layers=2, page_size=4, heads=2, head_dim=8,
                      capacity_bytes=8 * 1024)

    def rec(seed):
        rng = np.random.RandomState(seed)
        kq = rng.randint(-128, 127, (2, 4, 2, 8)).astype(np.int8)
        ks = rng.rand(2, 4).astype(np.float32)
        return kq, ks, kq.copy(), ks.copy()

    records = [rec(0), rec(1)]
    assert host.put_seq(7, records)
    assert host.pages_host == 2
    popped = host.pop_seq(7)
    assert len(popped) == 2 and host.pages_host == 0
    for a, b in zip(records, popped):       # verbatim int8 rows
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    # capacity accounting refuses what cannot fit
    assert not host.room_for(10 ** 6)
    # prefix spill is keyed and one-shot
    assert host.put_prefix(b"k1", rec(2))
    assert host.take_prefix(b"k1") is not None
    assert host.take_prefix(b"k1") is None


def test_host_page_bytes_are_the_closed_form_and_under_half_of_f32():
    """A page costs the host tier what the cost model's closed form
    says (what ``kv_offload_bytes`` charges a spilled page), which is
    the bytes of the int8 rows and f32 scales it holds and under half
    of the f32 page the device pool holds."""
    from paddle_tpu.static.cost_model import kv_offload_page_bytes

    page = 4
    host = HostKVPool(n_layers=CFG.n_layers, page_size=page,
                      heads=CFG.n_heads, head_dim=CFG.head_dim,
                      capacity_bytes=1 << 20)
    assert host.page_nbytes == kv_offload_page_bytes(CFG, page)
    rows = np.zeros((CFG.n_layers, page, CFG.n_heads, CFG.head_dim),
                    np.int8)
    scales = np.zeros((CFG.n_layers, page), np.float32)
    assert host.page_nbytes == 2 * (rows.nbytes + scales.nbytes)
    assert host.page_nbytes < 0.5 * 2 * rows.size * 4


def _offload_workload():
    plens = (9, 11, 9, 11, 9, 11)
    prompts = []
    for i in range(6):
        rng = np.random.RandomState(3000 + i)
        prompts.append([int(t) for t in rng.randint(0, CFG.vocab_size,
                                                    plens[i])])
    return prompts, 9


def test_park_resume_roundtrip_matches_big_pool_oracle(monkeypatch):
    """More concurrent sessions than the HBM pool can hold: the engine
    parks the coldest session into the host tier and resumes it with
    its KV restored — the tokens must equal a big-pool twin's."""
    prompts, new = _offload_workload()
    ref = _engine(monkeypatch, max_batch=3, n_pages=32, page_size=4,
                  max_pages_per_seq=5)
    ref_outs = []
    for p in prompts:
        h = ref.submit(p, max_new_tokens=new)
        _drive(ref)
        ref_outs.append(h.result(timeout=5))
    eng = _engine(monkeypatch, max_batch=3, n_pages=9, page_size=4,
                  max_pages_per_seq=5, host_kv_bytes=1 << 20)
    hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    _drive(eng)
    assert [h.result(timeout=5) for h in hs] == ref_outs
    c = eng.counters
    assert c.get("kv_sessions_parked", 0) >= 1
    assert c.get("kv_sessions_resumed", 0) >= 1
    assert c.get("kv_page_restores", 0) >= 1
    assert c.get("kv_offload_bytes", 0) > 0


def test_dry_pool_parks_with_tier_preempts_without(monkeypatch):
    """Same dry-pool workload twice: the tier-less engine can only
    preempt-requeue; the tiered engine parks instead — and both still
    produce identical tokens."""
    prompts, new = _offload_workload()
    outs = {}
    for tier in (0, 1 << 20):
        eng = _engine(monkeypatch, max_batch=3, n_pages=9, page_size=4,
                      max_pages_per_seq=5, host_kv_bytes=tier)
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        _drive(eng)
        outs[tier] = [h.result(timeout=5) for h in hs]
        if tier:
            assert eng.counters.get("kv_sessions_parked", 0) >= 1
        else:
            assert eng.counters.get("kv_sessions_parked", 0) == 0
    assert outs[0] == outs[1 << 20]


def test_killed_prefetch_falls_back_to_sync_restore(monkeypatch):
    """A dead restore-prefetch worker surfaces as KVRestoreError; the
    resume falls back to the synchronous h2d decode, counts the
    fallback, and the tokens are unaffected."""
    prompts, new = _offload_workload()
    eng = _engine(monkeypatch, max_batch=3, n_pages=9, page_size=4,
                  max_pages_per_seq=5, host_kv_bytes=1 << 20)

    def dead_take(key):
        raise KVRestoreError("prefetch worker died")

    monkeypatch.setattr(eng._prefetch, "take", dead_take)
    ref = _engine(monkeypatch, max_batch=3, n_pages=32, page_size=4,
                  max_pages_per_seq=5)
    ref_outs = []
    for p in prompts:
        h = ref.submit(p, max_new_tokens=new)
        _drive(ref)
        ref_outs.append(h.result(timeout=5))
    hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    _drive(eng)
    assert [h.result(timeout=5) for h in hs] == ref_outs
    assert eng.counters.get("kv_restore_fallbacks", 0) >= 1
