"""Multi-tier executor cache (repro.core.cache).

The tentpole properties:

- *tier parity*: the same DAG run cacheless, with a zero-capacity
  cache, memory-only, and memory+disk produces identical task results —
  the tiers change charged ms and cache_stats, never values. The
  zero-capacity cache is charge-identical to ``cache=None`` bit for bit.
- *eviction correctness*: an evicted-then-needed object is transparently
  re-fetched from the next tier (disk, then KV) with the right charges,
  including under injected task retries.
- *warm retention*: a warm container keeps its cache across reuses
  (tier-0 hits > 0 on shared-input DAGs); cold start and keep-alive
  expiry clear it.

The pinned cached runs (tests/test_determinism.py) hold their charges
bit-identical across changes, like every other charge in the system.
"""
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip without the dev extra
    from _hypothesis_fallback import given, settings, st

from repro.apps import gemm_dag, tree_reduction_dag
from repro.apps.tree_reduction import tree_reduction_expected
from repro.core import (
    ALL_PASSES,
    CacheConfig,
    CacheRegistry,
    CacheStats,
    CostModel,
    EngineConfig,
    ExecutorCache,
    FaultConfig,
    GraphBuilder,
    WukongEngine,
)
from repro.core.dag import TaskRef
from repro.platform import PlatformConfig


def drive(gen):
    """Run a cache effect generator to completion, collecting charges.
    Returns ``(return_value, [charged_ms, ...])``."""
    charges = []
    try:
        while True:
            eff = next(gen)
            assert eff[0] == "charge"
            charges.append(eff[1])
    except StopIteration as stop:
        return stop.value, charges


def seq_eval(dag):
    vals = {}
    for k in dag.topological_order():
        t = dag.tasks[k]
        args = [vals[a.key] if isinstance(a, TaskRef) else a
                for a in t.args]
        kwargs = {kk: vals[v.key] if isinstance(v, TaskRef) else v
                  for kk, v in t.kwargs.items()}
        vals[k] = t.fn(*args, **kwargs)
    return {k: vals[k] for k in dag.roots}


def random_dag(seed: int, n: int):
    import random

    rng = random.Random(seed)
    g = GraphBuilder()
    refs = []
    for i in range(n):
        k = rng.randint(0, min(4, len(refs)))
        deps = rng.sample(refs, k) if k else []
        if deps:
            refs.append(g.add(lambda *xs: sum(xs) + 1, *deps, name=f"n{i}"))
        else:
            refs.append(g.add((lambda v: (lambda: v))(i), name=f"n{i}"))
    return g.build()


# ---------------------------------------------------------------------------
# ExecutorCache unit behavior (drive the generators by hand — no clock)
# ---------------------------------------------------------------------------


class TestExecutorCacheUnit:
    def test_mem_hit_is_free_and_counted(self):
        c = ExecutorCache(CacheConfig(memory_bytes=100, disk_bytes=1000))
        _, ch = drive(c.deposit_g("k", "v", 10))
        assert ch == []  # fits tier 0: nothing charged
        (hit, val), ch = drive(c.probe_g("k"))
        assert hit and val == "v" and ch == []  # tier-0 hit: free
        assert c.stats.mem_hits == 1 and c.stats.bytes_local == 10

    def test_probe_miss_charges_nothing(self):
        c = ExecutorCache(CacheConfig(memory_bytes=100, disk_bytes=1000))
        (hit, val), ch = drive(c.probe_g("absent"))
        assert not hit and val is None and ch == []
        assert c.stats.misses == 1

    def test_lru_spill_and_disk_promotion_charges(self):
        cfg = CacheConfig(memory_bytes=25, disk_bytes=1000)
        c = ExecutorCache(cfg)
        drive(c.deposit_g("a", "A", 10))
        drive(c.deposit_g("b", "B", 10))
        # touch "a" so "b" becomes the LRU victim
        drive(c.probe_g("a"))
        _, ch = drive(c.deposit_g("c", "C", 10))
        assert ch == [cfg.disk_write_ms(10)]  # spill of "b" charged
        assert c.stats.spills == 1 and c.stats.mem_evictions == 1
        # disk hit: charged read, promoted back to memory (evicting the
        # new LRU "a", whose spill is charged in the same step)
        (hit, val), ch = drive(c.probe_g("b"))
        assert hit and val == "B"
        assert ch == [cfg.disk_read_ms(10) + cfg.disk_write_ms(10)]
        assert c.stats.disk_hits == 1 and c.stats.bytes_disk == 10
        (hit, _), _ = drive(c.probe_g("a"))  # now served from disk
        assert hit and c.stats.disk_hits == 2

    def test_deposit_existing_is_lru_touch_not_duplicate(self):
        c = ExecutorCache(CacheConfig(memory_bytes=25, disk_bytes=1000))
        drive(c.deposit_g("a", "A", 10))
        drive(c.deposit_g("b", "B", 10))
        drive(c.deposit_g("a", "A", 10))  # refresh: "b" is now LRU
        drive(c.deposit_g("c", "C", 10))
        assert c.mem_bytes == 20 and c.stats.spills == 1
        (hit, _), ch = drive(c.probe_g("a"))
        assert hit and ch == []  # "a" stayed in memory

    def test_disk_eviction_drops_oldest(self):
        c = ExecutorCache(CacheConfig(memory_bytes=10, disk_bytes=20))
        for k in ("a", "b", "c"):
            drive(c.deposit_g(k, k.upper(), 10))
        # "a" then "b" spilled; depositing "c" keeps mem, so disk holds
        # a+b at capacity. One more spill evicts "a" from disk.
        drive(c.deposit_g("d", "D", 10))
        assert c.stats.disk_evictions == 1
        (hit, _), _ = drive(c.probe_g("a"))
        assert not hit  # dropped from the whole hierarchy

    def test_too_large_for_disk_is_not_cached(self):
        c = ExecutorCache(CacheConfig(memory_bytes=10, disk_bytes=20))
        _, ch = drive(c.deposit_g("big", "X", 50))
        assert ch == []  # exceeds both tiers: charge nothing
        assert len(c) == 0
        (hit, _), _ = drive(c.probe_g("big"))
        assert not hit

    def test_zero_capacity_all_ops_chargeless(self):
        c = ExecutorCache(CacheConfig(memory_bytes=0, disk_bytes=0))
        _, ch = drive(c.deposit_g("k", "v", 1))
        assert ch == []
        (hit, _), ch = drive(c.probe_g("k"))
        assert not hit and ch == []
        assert len(c) == 0

    def test_invalidate_prefix_reclaims_both_tiers(self):
        c = ExecutorCache(CacheConfig(memory_bytes=10, disk_bytes=1000))
        drive(c.deposit_g("j1::a", "A", 10))
        drive(c.deposit_g("j1::b", "B", 10))  # spills j1::a to disk
        drive(c.deposit_g("j2::c", "C", 10))  # spills j1::b to disk
        assert c.invalidate_prefix("j1::") == 2
        assert not c.contains("j1::a") and not c.contains("j1::b")
        assert c.contains("j2::c")
        assert c.mem_bytes == 10 and c.disk_bytes == 0

    def test_resident_bytes_scores_both_tiers(self):
        c = ExecutorCache(CacheConfig(memory_bytes=10, disk_bytes=1000))
        drive(c.deposit_g("a", "A", 10))
        drive(c.deposit_g("b", "B", 10))  # "a" spills
        assert c.resident_bytes(["a", "b", "absent"]) == 20

    def test_config_validation(self):
        with pytest.raises(ValueError):
            CacheConfig(memory_bytes=-1)
        with pytest.raises(ValueError):
            CacheConfig(disk_read_mbps=0)
        with pytest.raises(ValueError):
            CacheConfig(disk_base_ms=-0.1)


class TestCacheRegistry:
    def test_cache_follows_container_and_drop_retires_stats(self):
        r = CacheRegistry(CacheConfig(memory_bytes=100, disk_bytes=100))
        c = r.cache_for("fn", 1)
        assert r.cache_for("fn", 1) is c  # warm reuse: same cache
        assert r.cache_for("fn", 2) is not c
        drive(c.deposit_g("k", "v", 10))
        drive(c.probe_g("k"))
        r.drop("fn", 1)
        assert r.get("fn", 1) is None
        snap = r.snapshot()  # retired stats survive the container
        assert snap["mem_hits"] == 1 and snap["deposits"] == 1
        assert snap["containers"] == 1  # only ("fn", 2) lives

    def test_invalidate_prefix_reaches_every_container(self):
        r = CacheRegistry(CacheConfig(memory_bytes=100, disk_bytes=100))
        drive(r.cache_for("fn", 1).deposit_g("j::a", "A", 10))
        drive(r.cache_for("fn", 2).deposit_g("j::b", "B", 10))
        assert r.invalidate_prefix("j::") == 2
        assert r.snapshot()["resident_mem_bytes"] == 0

    def test_per_job_sink_counts_alongside_container_stats(self):
        c = ExecutorCache(CacheConfig(memory_bytes=100, disk_bytes=100))
        sink = CacheStats()
        drive(c.deposit_g("k", "v", 10, stats=sink))
        drive(c.probe_g("k", stats=sink))
        drive(c.probe_g("nope", stats=sink))
        assert sink.snapshot() == c.stats.snapshot()
        assert sink.mem_hits == 1 and sink.misses == 1


# ---------------------------------------------------------------------------
# Engine-level: tier parity, eviction, retention
# ---------------------------------------------------------------------------


def _cfg(cache, **kw):
    kw.setdefault("num_initial_invokers", 4)
    kw.setdefault("num_proxy_invokers", 4)
    return EngineConfig(
        cost=CostModel(cold_start_ms=250.0),
        platform=PlatformConfig(keep_alive_s=600.0, cache=cache),
        **kw)


TIERS = [
    ("cacheless", None),
    ("zero", CacheConfig(memory_bytes=0, disk_bytes=0)),
    ("mem_only", CacheConfig(memory_bytes=64 << 20, disk_bytes=0)),
    ("mem_disk", CacheConfig(memory_bytes=64 << 20, disk_bytes=512 << 20)),
    ("tiny_mem", CacheConfig(memory_bytes=1 << 10, disk_bytes=512 << 20)),
]


class TestTierParity:
    def test_zero_capacity_cache_is_charge_identical_to_cacheless(self):
        dag = tree_reduction_dag(64, payload_bytes=1 << 16, compute_ms=5.0)
        r0 = WukongEngine(_cfg(None)).compute(dag)
        r1 = WukongEngine(
            _cfg(CacheConfig(memory_bytes=0, disk_bytes=0))).compute(dag)
        assert r0.charged_ms == r1.charged_ms
        assert r0.wall_s == r1.wall_s
        assert r0.kv_stats == r1.kv_stats
        assert r0.cache_stats == {}  # cacheless: no block at all
        assert r1.cache_stats["mem_hits"] == 0
        assert r1.cache_stats["disk_hits"] == 0

    @pytest.mark.parametrize("label,cache", TIERS)
    def test_tree_reduction_identical_results_across_tiers(self, label,
                                                           cache):
        dag = tree_reduction_dag(32, payload_bytes=1 << 14, compute_ms=2.0)
        rep = WukongEngine(_cfg(cache)).compute(dag)
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(32)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), n=st.integers(2, 40))
    def test_random_dags_tier_parity(self, seed, n):
        """Property: tiers change charges and cache_stats, never values."""
        dag = random_dag(seed, n)
        expected = seq_eval(dag)
        for _, cache in TIERS:
            assert WukongEngine(_cfg(cache)).compute(dag).results == expected


class TestEvictionCorrectness:
    def test_spills_happen_and_results_stay_correct(self):
        # 16 KiB payloads against a 1 KiB tier 0: every deposit
        # overflows to disk; fan-in completers re-fetch through tier 1.
        dag = tree_reduction_dag(64, payload_bytes=1 << 14, compute_ms=2.0)
        rep = WukongEngine(
            _cfg(CacheConfig(memory_bytes=1 << 10,
                             disk_bytes=512 << 20))).compute(dag)
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(64)
        cs = rep.cache_stats
        assert cs["spills"] > 0 and cs["mem_evictions"] > 0

    def test_evicted_from_disk_too_falls_through_to_kv(self):
        # Tier 1 smaller than one payload: nothing is cacheable at all;
        # every read falls through to the KV store and still resolves.
        dag = tree_reduction_dag(32, payload_bytes=1 << 14, compute_ms=2.0)
        rep = WukongEngine(
            _cfg(CacheConfig(memory_bytes=1 << 10,
                             disk_bytes=1 << 10))).compute(dag)
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(32)
        assert rep.cache_stats["mem_hits"] == 0
        assert rep.cache_stats["disk_hits"] == 0

    def test_retries_with_tiny_cache_stay_correct_and_identical(self):
        # Injected failures + Lambda retries against a spilling cache:
        # the retry re-walks from its start key; host-side mutation is
        # atomic under the cache lock, so it never observes a
        # half-spilled entry — results and charges stay deterministic.
        cfg = EngineConfig(
            cost=CostModel(cold_start_ms=250.0),
            platform=PlatformConfig(
                keep_alive_s=600.0,
                cache=CacheConfig(memory_bytes=1 << 12,
                                  disk_bytes=512 << 20)),
            faults=FaultConfig(task_failure_prob=0.08, max_retries=2,
                               seed=11, retry_backoff_base_ms=100.0),
            num_initial_invokers=4, num_proxy_invokers=4)
        rep = WukongEngine(cfg).compute(
            tree_reduction_dag(64, payload_bytes=1 << 14, compute_ms=2.0))
        (_, root), = rep.results.items()
        assert float(root[0]) == tree_reduction_expected(64)
        assert rep.fault_stats["task_retries"] > 0
        rep2 = WukongEngine(cfg).compute(
            tree_reduction_dag(64, payload_bytes=1 << 14, compute_ms=2.0))
        assert rep.charged_ms == rep2.charged_ms
        assert rep.cache_stats == rep2.cache_stats


class TestWarmRetention:
    """A warm container RETAINS its cache; cold start / expiry clear it."""

    def test_shared_input_dag_hits_tier0_across_reuses(self):
        # GEMM: every A/B block feeds b multiply tasks. Read-through
        # caching + hint-steered placement turn warm reuse into tier-0
        # hits on the shared blocks.
        dag = gemm_dag(512, 128)
        rep = WukongEngine(_cfg(CacheConfig(),
                                optimize=ALL_PASSES)).compute(dag)
        cs = rep.cache_stats
        assert cs["mem_hits"] > 0 and cs["bytes_local"] > 0
        assert rep.platform_stats["cache"]["mem_hits"] >= cs["mem_hits"]

    def test_zero_keep_alive_clears_cache_every_invocation(self):
        # keep_alive 0: every container is reclaimed on release, its
        # cache with it. Hits within one invocation survive (a re-read
        # of an input the same walk already fetched IS local), but the
        # cross-invocation hits that warm retention adds disappear —
        # and no cache outlives the run.
        dag = gemm_dag(512, 128)

        def run(keep_alive_s):
            cfg = EngineConfig(
                cost=CostModel(cold_start_ms=250.0),
                platform=PlatformConfig(keep_alive_s=keep_alive_s,
                                        cache=CacheConfig()),
                optimize=ALL_PASSES,
                num_initial_invokers=4, num_proxy_invokers=4)
            return WukongEngine(cfg).compute(dag)

        cold, warm = run(0.0), run(600.0)
        assert cold.cache_stats["mem_hits"] < warm.cache_stats["mem_hits"]
        assert cold.platform_stats["cache"]["containers"] == 0
        assert warm.platform_stats["cache"]["containers"] > 0

    def test_cache_block_absent_without_cache_config(self):
        dag = tree_reduction_dag(16, compute_ms=2.0)
        rep = WukongEngine(_cfg(None)).compute(dag)
        assert "cache" not in rep.platform_stats
        assert rep.cache_stats == {}
