"""Serving tier (DESIGN.md §9): paged KV cache, router, traffic, sim.

The load-bearing test is the lockstep equivalence: a dense Server and a
paged Server driven over the same ragged two-wave workload must emit
bit-identical tokens AND bit-identical logits at every step — the paged
cache is a memory-layout change, not a numerics change.  The second wave
re-admits into recycled slots whose pages hold stale KV from the first
wave, which is exactly the case that corrupts silently if page zeroing /
overwrite-at-admission is wrong.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.cost_model import (ClusterSpec, DeviceGroup, P100_16G,
                                   T4_16G, V100_PAPER, lm_serving_meta,
                                   prefill_time, decode_step_time,
                                   serving_page_budget)
from repro.kernels.autotune import DEFAULT_TILES, autotune
from repro.core.planner import compile_plan
from repro.core.sharding import make_mesh
from repro.serving.metrics import RequestTiming, ServeMetrics, percentile
from repro.serving.paged_cache import (BlockTable, PageAllocator,
                                       PagedCacheConfig)
from repro.serving.router import route
from repro.serving.server import Request, Server, prompt_bucket
from repro.serving.sim import ServeScenario, compare
from repro.serving.traffic import TrafficCfg, make_trace


# ---------------------------------------------------------------------------
# paged_cache: allocator + block table (pure host-side, no jax)
# ---------------------------------------------------------------------------

def _pcfg(n_pages=9, page_size=4, max_pages=4):
    return PagedCacheConfig(n_pages, page_size, max_pages)


def test_paged_cache_config_geometry():
    cfg = _pcfg()
    assert cfg.max_len == 16
    assert cfg.usable_pages == 8
    assert cfg.pages_for(1) == 1
    assert cfg.pages_for(4) == 1
    assert cfg.pages_for(5) == 2
    with pytest.raises(ValueError):
        PagedCacheConfig(1, 4, 4)        # needs a trash page + one real


def test_allocator_all_or_nothing():
    alloc = PageAllocator(_pcfg())
    pages = alloc.alloc(0, 3)
    assert len(pages) == 3 and 0 not in pages       # never the trash page
    assert alloc.free_pages == 5
    with pytest.raises(MemoryError):
        alloc.alloc(1, 6)                # only 5 left: nothing granted
    assert alloc.free_pages == 5
    assert alloc.owned(1) == []


def test_allocator_free_recycles_and_guards_double_free():
    alloc = PageAllocator(_pcfg())
    first = alloc.alloc(0, 2)
    alloc.free_slot(0)
    assert alloc.free_pages == 8
    again = alloc.alloc(1, 2)
    assert set(again) == set(first)       # LIFO reuse of the freed pages
    alloc._owned[2] = [again[0]]          # simulate corrupt ownership
    alloc.free_slot(1)
    with pytest.raises(RuntimeError):
        alloc.free_slot(2)                # its page is already free


def test_block_table_assign_append_needs():
    cfg = _pcfg()
    bt = BlockTable(slots=2, cfg=cfg)
    bt.assign(0, [3, 5], pos=7)
    assert list(bt.table[0]) == [3, 5, 0, 0]
    assert not bt.needs_page(0)           # pos 7 lands in page 1 (=5)
    bt.pos[0] = 8
    assert bt.needs_page(0)               # page 2 unallocated
    bt.append_page(0, 7)
    assert not bt.needs_page(0)
    bt.clear(0)
    assert not bt.table[0].any() and bt.pos[0] == 0
    with pytest.raises(ValueError):
        bt.assign(1, [1, 2, 3, 4, 5], pos=0)


# ---------------------------------------------------------------------------
# metrics + traffic
# ---------------------------------------------------------------------------

def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.random(101).tolist()
    for p in (0, 25, 50, 90, 99, 100):
        assert percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    with pytest.raises(ValueError):
        percentile([], 50)


def test_request_timing_slos():
    tm = RequestTiming(rid=0, arrival=1.0, admitted=2.0, first_token=3.0,
                       finished=7.0, n_tokens=5)
    assert tm.ttft == 2.0
    assert tm.tpot == 1.0
    assert tm.e2e == 6.0
    m = ServeMetrics()
    with pytest.raises(ValueError):
        m.add(RequestTiming(rid=1, arrival=0.0))


def test_traffic_trace_deterministic_and_calibrated():
    cfg = TrafficCfg(rate=50.0, n_requests=20000)
    a, b = make_trace(cfg, seed=3), make_trace(cfg, seed=3)
    assert a == b
    assert make_trace(cfg, seed=4) != a
    ts = [x.t for x in a]
    assert ts == sorted(ts)
    # Pareto gaps with x_m=(α−1)/(α·rate) have mean 1/rate
    assert ts[-1] / len(ts) == pytest.approx(1 / 50.0, rel=0.1)
    assert {x.prompt_len for x in a} <= set(cfg.prompt_lens)
    assert {x.gen_len for x in a} <= set(cfg.gen_lens)


# ---------------------------------------------------------------------------
# router: prefill→compute-rich, decode→bandwidth-rich
# ---------------------------------------------------------------------------

def _mixed_spec():
    return ClusterSpec(groups=(DeviceGroup("8xv100", V100_PAPER, 8),
                               DeviceGroup("8xt4", T4_16G, 8)))


def test_router_splits_by_roofline():
    meta = lm_serving_meta(get_config("tinyllama-1.1b"))
    plan = route(meta, _mixed_spec(), mean_prompt=64, mean_gen=64,
                 page_size=64, batch_slots=16)
    # T4s are compute-rich per HBM byte → prefill; V100s have 3× the
    # memory bandwidth → decode
    assert {g.name for g in plan.prefill.groups} == {"8xt4"}
    assert {g.name for g in plan.decode.groups} == {"8xv100"}
    assert plan.request_rate > 0
    assert plan.page_budget > 0
    assert plan.concurrency > 0


def test_router_rejects_single_group():
    meta = lm_serving_meta(get_config("tinyllama-1.1b"))
    with pytest.raises(ValueError):
        route(meta, ClusterSpec.homogeneous(V100_PAPER, 8),
              mean_prompt=64, mean_gen=64, page_size=64, batch_slots=16)


def test_serving_rooflines_monotone():
    meta = lm_serving_meta(get_config("tinyllama-1.1b"))
    g = DeviceGroup("v100", V100_PAPER, 8)
    assert prefill_time(meta, g, 256) > prefill_time(meta, g, 64)
    assert decode_step_time(meta, g, 8, 8 * 2048) \
        > decode_step_time(meta, g, 8, 8 * 128)
    assert serving_page_budget(meta, g, 64) \
        > serving_page_budget(meta, g, 64, reserve=0.5)


# ---------------------------------------------------------------------------
# analytic simulator
# ---------------------------------------------------------------------------

def test_sim_conserves_requests_and_flagship_wins():
    meta = lm_serving_meta(get_config("tinyllama-1.1b"))
    plan = route(meta, _mixed_spec(), mean_prompt=60, mean_gen=74,
                 page_size=64, batch_slots=64)
    sc = ServeScenario(
        name="t", spec=_mixed_spec(),
        traffic=TrafficCfg(rate=0.8 * plan.request_rate, n_requests=400,
                           gen_lens=(32, 64, 128)),
        batch_slots=64, page_size=64, max_len=4096)
    r = compare(meta, sc)
    assert r["colocated"]["completed"] == 400
    assert r["disagg"]["completed"] == 400
    assert r["tokens_per_s_ratio"] > 1.0
    assert r["ttft_p99_ratio"] <= 1.0


# ---------------------------------------------------------------------------
# autotuner: per-hardware page size
# ---------------------------------------------------------------------------

def test_autotuned_page_size():
    assert DEFAULT_TILES.page_size == 64
    kw = dict(head_dim=128, group=4, d_model=2048)
    v100 = autotune(V100_PAPER, **kw).page_size
    p100 = autotune(P100_16G, **kw).page_size
    assert 8 <= p100 <= v100 <= 256       # monotone in VMEM budget
    t = dataclasses.replace(V100_PAPER, vmem_bytes=2 * V100_PAPER.vmem_bytes)
    assert autotune(t, **kw).page_size >= v100


# ---------------------------------------------------------------------------
# jax-level: prompt bucketing + paged ↔ dense lockstep equivalence
# ---------------------------------------------------------------------------

MAX_LEN = 32
PAGE = 8
SLOTS = 3


@pytest.fixture(scope="module")
def served():
    cfg = get_config("tinyllama-1.1b", smoke=True)
    from repro.models.lm import build
    model = build(cfg)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    plan = compile_plan(model, mesh)
    with mesh:
        params = plan.init_params(jax.random.key(0))
    return model, plan, params


def test_prompt_bucket_pow2():
    assert prompt_bucket(1, 64) == 8
    assert prompt_bucket(8, 64) == 8
    assert prompt_bucket(9, 64) == 16
    assert prompt_bucket(33, 64) == 64
    assert prompt_bucket(64, 64) == 64
    with pytest.raises(ValueError):
        prompt_bucket(65, 64)


@pytest.mark.slow
def test_prefill_jit_cache_bounded(served):
    """S1 regression: admitting every prompt length 3..20 must compile
    O(log max_len) prefill programs (buckets {8, 16, 32}), not one per
    distinct length."""
    model, plan, params = served
    server = Server(model, plan, batch_slots=2, max_len=MAX_LEN)
    for i, s in enumerate(range(3, 21)):
        prompt = np.arange(s, dtype=np.int32) % model.cfg.vocab
        # max_new=1 → finishes at admission, the slot never fills
        server.admit(params, Request(i, prompt, max_new=1), slot=0)
    assert server.prefill_cache_size <= 3
    assert set(server._prefill_fns) <= {8, 16, 32}


def _drive_lockstep(model, plan, params, requests_spec):
    """Run dense and paged servers over the same workload in lockstep,
    asserting bit-identical tokens and logits at every step."""
    servers = {
        "dense": Server(model, plan, batch_slots=SLOTS, max_len=MAX_LEN,
                        cache="dense", record_logits=True),
        "paged": Server(model, plan, batch_slots=SLOTS, max_len=MAX_LEN,
                        cache="paged", page_size=PAGE, record_logits=True),
    }
    pendings = {arm: [Request(i, p.copy(), max_new=g)
                      for i, (p, g) in enumerate(requests_spec)]
                for arm in servers}
    dones = {arm: [] for arm in servers}
    for _ in range(10_000):
        if not any(pendings[a] or servers[a].active for a in servers):
            break
        active_sets = {}
        for arm, srv in servers.items():
            pending = pendings[arm]
            while (pending and (slot := srv.free_slot()) is not None
                   and srv.can_admit(pending[0])):
                req = pending.pop(0)
                srv.admit(params, req, slot)
                if req.done:
                    dones[arm].append(req)
            active_sets[arm] = tuple(b for b, r in enumerate(srv.slots)
                                     if r is not None)
        assert active_sets["dense"] == active_sets["paged"]
        for arm, srv in servers.items():
            dones[arm].extend(srv.step(params))
            pendings[arm][:0] = srv.take_requeued()
        for b in active_sets["dense"]:
            assert np.array_equal(servers["dense"].last_logits[b],
                                  servers["paged"].last_logits[b]), \
                f"slot {b}: paged logits diverged from dense"
    else:
        raise AssertionError("lockstep drive did not converge")
    return servers, dones


@pytest.mark.slow
def test_paged_equals_dense_lockstep_two_waves(served):
    """S3: ragged prompts, more requests than slots — the second wave
    re-admits into recycled slots whose pages hold stale first-wave KV.
    Tokens and per-step logits must be bit-identical (fp32)."""
    model, plan, params = served
    rng = np.random.default_rng(7)
    spec = [(rng.integers(0, model.cfg.vocab, s, dtype=np.int32), g)
            for s, g in [(3, 6), (7, 9), (12, 5),      # wave 1 (ragged)
                         (5, 8), (9, 4), (16, 7)]]     # wave 2 (recycled)
    servers, dones = _drive_lockstep(model, plan, params, spec)
    assert len(dones["dense"]) == len(dones["paged"]) == len(spec)
    by_rid = {arm: {r.rid: r for r in dones[arm]} for arm in dones}
    for rid in by_rid["dense"]:
        assert by_rid["dense"][rid].out_tokens \
            == by_rid["paged"][rid].out_tokens, f"request {rid} diverged"
        assert np.array_equal(by_rid["dense"][rid].first_logits,
                              by_rid["paged"][rid].first_logits)
    # the trash page stayed exactly zero (live-mask on the scatter)
    for name in servers["paged"].pools:
        for kv in ("k", "v"):
            page0 = np.asarray(servers["paged"].pools[name][kv][:, 0])
            assert not page0.any()


@pytest.mark.slow
def test_paged_preemption_still_exact(served):
    """Pool too small for every slot's full sequence: decode-time page
    appends preempt the youngest slot, it restarts, and the final tokens
    still match the dense arm exactly (dense never preempts — only the
    schedule differs, so compare converged out_tokens per request)."""
    model, plan, params = served
    rng = np.random.default_rng(11)
    spec = [(rng.integers(0, model.cfg.vocab, 6, dtype=np.int32), 14)
            for _ in range(3)]
    dense = Server(model, plan, batch_slots=SLOTS, max_len=MAX_LEN,
                   cache="dense")
    # 7 usable pages of 8 rows; 3 slots × ceil(20/8)=3 pages don't fit
    paged = Server(model, plan, batch_slots=SLOTS, max_len=MAX_LEN,
                   cache="paged", page_size=PAGE, n_pages=8)
    results = {}
    for arm, srv in (("dense", dense), ("paged", paged)):
        pending = [Request(i, p.copy(), max_new=g)
                   for i, (p, g) in enumerate(spec)]
        done = []
        for _ in range(10_000):
            if not (pending or srv.active):
                break
            while (pending and (slot := srv.free_slot()) is not None
                   and srv.can_admit(pending[0])):
                req = pending.pop(0)
                srv.admit(params, req, slot)
                if req.done:
                    done.append(req)
            done.extend(srv.step(params))
            pending[:0] = srv.take_requeued()
        else:
            raise AssertionError("drive did not converge")
        results[arm] = {r.rid: r for r in done}
    assert sum(r.preemptions for r in results["paged"].values()) > 0, \
        "tight pool never preempted — the scenario lost its point"
    for rid, r in results["dense"].items():
        assert r.out_tokens == results["paged"][rid].out_tokens, \
            f"request {rid}: tokens diverged after preemption/restart"


@pytest.mark.slow
def test_pallas_paged_decode_matches_ref(served):
    """The Pallas gather-decode kernel (interpret mode on CPU) against a
    straight jnp reference over the same block table."""
    from repro.kernels.flash_attention import paged_decode
    B, H, K, D, ps, mp, P = 2, 4, 2, 16, 4, 3, 7
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k_pool = jax.random.normal(ks[1], (P, K, ps, D), jnp.float32)
    v_pool = jax.random.normal(ks[2], (P, K, ps, D), jnp.float32)
    table = jnp.array([[2, 5, 0], [1, 3, 6]], jnp.int32)
    pos = jnp.array([6, 9], jnp.int32)

    out = paged_decode(q, k_pool, v_pool, table, pos, interpret=True)

    # reference: gather pages logically, mask, softmax
    G = H // K
    kg = jnp.swapaxes(k_pool[table], 2, 3).reshape(B, mp * ps, K, D)
    vg = jnp.swapaxes(v_pool[table], 2, 3).reshape(B, mp * ps, K, D)
    qr = q.reshape(B, K, G, D) * (D ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, kg)
    mask = jnp.arange(mp * ps)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bkgs,bskd->bkgd", p, vg).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_server_rejects_bad_geometry(served):
    model, plan, _ = served
    with pytest.raises(ValueError):
        Server(model, plan, batch_slots=2, max_len=30, cache="paged",
               page_size=8)              # max_len not a page multiple
    with pytest.raises(ValueError):
        Server(model, plan, batch_slots=2, max_len=32, cache="nope")
