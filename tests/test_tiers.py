"""Tiered chunk storage: flat-store equivalence, promotion/demotion
semantics, tier-aware planning, and the serving-layer prefetch path."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AccessLog,
    ChunkStore,
    TieredChunkStore,
    TierSpec,
    ZygoteRegistry,
    flatten_pytree,
)
from repro.core.planner import (
    StorageModel,
    TieredStorageModel,
    TierModel,
    predict,
)
from repro.core.tiers import RamCacheTier, TierReadStats

CHUNK = 4096

# fast remote throttle for tests: semantics, not timing
FAST_REMOTE = dict(remote_bw=10e9, remote_lat=0.0)


def _payloads(rng, n, max_size=9000, nzero=2):
    out = []
    for i in range(n):
        size = int(rng.integers(1, max_size))
        if i < nzero:
            out.append(b"\x00" * size)
        else:
            out.append(rng.integers(0, 255, size, dtype=np.uint8).tobytes())
    return out


def _fill(store, payloads, pack_id="p0"):
    pack = store.open_pack(pack_id)
    refs = store.put_chunks(pack, payloads)
    pack.close()
    store.save_index()
    return refs


# ------------------------------------------------------------- RAM cache tier

class TestRamCacheTier:
    def test_lru_eviction_bounded(self):
        tier = RamCacheTier(capacity_bytes=10)
        assert tier.put("a", b"xxxx") and tier.put("b", b"yyyy")
        assert tier.put("c", b"zzzz")  # evicts "a" (LRU)
        assert tier.used <= 10
        assert tier.get("a") is None
        assert tier.get("b") == b"yyyy"
        assert tier.evictions == 1

    def test_oversized_payload_refused(self):
        tier = RamCacheTier(capacity_bytes=4)
        assert not tier.put("big", b"12345")
        assert tier.used == 0

    def test_access_refreshes_lru_order(self):
        tier = RamCacheTier(capacity_bytes=8)
        tier.put("a", b"1111")
        tier.put("b", b"2222")
        tier.get("a")  # now "b" is LRU
        tier.put("c", b"3333")
        assert tier.get("b") is None
        assert tier.get("a") == b"1111"


# ---------------------------------------------------- flat-store equivalence

class TestTieredEquivalence:
    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        ram_bytes=st.sampled_from([0, 1, 6000, 12000, 1 << 20]),
        n_demote=st.integers(0, 12),
        promote=st.booleans(),
    )
    def test_read_batch_into_matches_flat_store(
        self, tmp_path_factory, seed, ram_bytes, n_demote, promote
    ):
        """INVARIANT: whatever the cache capacity, eviction pressure, or
        remote residency, the tiered scatter-read returns byte-identical
        content to a flat ChunkStore holding the same payloads."""
        tmp = tmp_path_factory.mktemp("eq")
        rng = np.random.default_rng(seed)
        payloads = _payloads(rng, 12)

        flat = ChunkStore(str(tmp / "flat"))
        refs = _fill(flat, payloads)
        tiered = TieredChunkStore(
            str(tmp / "tiered"),
            spec=TierSpec(ram_bytes=ram_bytes, **FAST_REMOTE),
        )
        refs2 = _fill(tiered, payloads)
        assert [r.digest for r in refs] == [r.digest for r in refs2]
        # scatter residency: demote a random subset to the remote tier
        order = rng.permutation(len(refs))[:n_demote]
        tiered.demote([refs[i] for i in order])

        # duplicate some refs so the dedupe/replicate path is exercised
        req = list(refs) + [refs[int(rng.integers(0, len(refs)))]]
        expect = {}
        bufs_flat = [bytearray(r.size) for r in req]
        flat.read_batch_into([(r, memoryview(b)) for r, b in zip(req, bufs_flat)])
        bufs_tier = [bytearray(r.size) for r in req]
        stats = TierReadStats()
        tiered.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(req, bufs_tier)],
            stats=stats, promote=promote,
        )
        for r, bf, bt in zip(req, bufs_flat, bufs_tier):
            assert bytes(bf) == bytes(bt), r.digest
        tiered.join_promotions()
        # and again after promotion settled (chunks may have moved tiers)
        bufs_tier2 = [bytearray(r.size) for r in req]
        tiered.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(req, bufs_tier2)]
        )
        for bf, bt in zip(bufs_flat, bufs_tier2):
            assert bytes(bf) == bytes(bt)
        flat.close()
        tiered.close()

    def test_parallel_ram_copy_path_byte_identical(self, tmp_path):
        """RAM reads above _RAM_PARALLEL_BYTES fan ctypes.memmove across
        the I/O pool — content must match the serial path exactly."""
        rng = np.random.default_rng(7)
        cb = 256 * 1024
        payloads = [rng.integers(0, 255, cb, dtype=np.uint8).tobytes()
                    for _ in range(40)]  # 10 MiB: well past the threshold
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 30)
        )
        refs = _fill(store, payloads)
        store.prefetch(refs)
        buf = np.zeros(len(payloads) * cb, dtype=np.uint8)
        mv = memoryview(buf)
        dests = [(r, mv[i * cb:(i + 1) * cb]) for i, r in enumerate(refs)]
        stats = TierReadStats()
        n = store.read_batch_into(dests, stats=stats)
        assert n == len(payloads) * cb
        assert stats.tier_bytes == {"ram": n}
        for i, p in enumerate(payloads):
            assert bytes(mv[i * cb:(i + 1) * cb]) == p
        # serial path agrees
        buf2 = np.zeros_like(buf)
        mv2 = memoryview(buf2)
        store.read_batch_into(
            [(r, mv2[i * cb:(i + 1) * cb]) for i, r in enumerate(refs)],
            parallel=False,
        )
        assert bytes(mv) == bytes(mv2)
        store.close()

    def test_get_chunk_and_read_batch_tier_aware(self, tmp_path):
        rng = np.random.default_rng(0)
        payloads = _payloads(rng, 8)
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        refs = _fill(store, payloads)
        store.demote(refs[4:])
        store.prefetch(refs[2:3])   # refs[:2] are zero chunks
        bufs = [bytearray(r.size) for r in refs]
        stats = TierReadStats()
        store.read_batch_into([(r, memoryview(b)) for r, b in zip(refs, bufs)],
                              stats=stats, promote=False)
        assert bufs == [bytearray(p) for p in payloads]
        assert set(stats.tier_bytes) == {"ram", "local", "remote"}
        assert sum(stats.tier_bytes.values()) == sum(
            r.size for r in refs if not r.zero)
        for r, p in zip(refs, payloads):
            assert store.get_chunk(r) == p


# ----------------------------------------------------- promotion / demotion

class TestTierMovement:
    def test_demote_then_fetch_promotes_downward(self, tmp_path):
        rng = np.random.default_rng(1)
        payloads = _payloads(rng, 6, nzero=0)
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        refs = _fill(store, payloads)
        moved = store.demote(refs)
        assert moved == sum(len(p) for p in payloads)
        assert all(store.tier_of(r.digest) == "remote" for r in refs)
        epoch0 = store.residency_epoch

        bufs = [bytearray(r.size) for r in refs]
        stats = TierReadStats()
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs)], stats=stats
        )
        store.join_promotions()
        assert stats.tier_bytes.get("remote") == moved
        assert store.residency_epoch > epoch0
        # promoted: now resident warm (ram first, local behind it)
        assert all(store.tier_of(r.digest) == "ram" for r in refs)
        assert store.promoted_bytes == moved
        # a second read is served entirely from the warm tiers
        stats2 = TierReadStats()
        bufs2 = [bytearray(r.size) for r in refs]
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs2)], stats=stats2
        )
        assert "remote" not in stats2.tier_bytes
        assert bufs == bufs2

    def test_promote_false_pins_chunks_remote(self, tmp_path):
        rng = np.random.default_rng(2)
        payloads = _payloads(rng, 4, nzero=0)
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        refs = _fill(store, payloads)
        store.demote(refs)
        bufs = [bytearray(r.size) for r in refs]
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs)], promote=False
        )
        store.join_promotions()
        assert all(store.tier_of(r.digest) == "remote" for r in refs)
        assert store.promoted_bytes == 0

    def test_prefetch_with_ram_disabled_is_counted_noop(self, tmp_path):
        """With no RAM tier, local chunks are already as warm as the
        hierarchy gets: prefetch must not read, count, or bump the epoch."""
        rng = np.random.default_rng(5)
        store = TieredChunkStore(str(tmp_path / "s"),
                                 spec=TierSpec(ram_bytes=0))
        refs = _fill(store, _payloads(rng, 5, nzero=0))
        epoch = store.residency_epoch
        stats = store.prefetch(refs)
        assert stats.prefetched_chunks == 0
        assert stats.prefetched_bytes == 0
        assert store.residency_epoch == epoch

    def test_accounting_is_union_across_pack_tiers(self, tmp_path):
        """Demotion moves bytes, promotion copies them — logical
        stored_bytes/num_chunks must stay constant through both."""
        rng = np.random.default_rng(6)
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        refs = _fill(store, _payloads(rng, 5, nzero=0))
        before, n = store.stored_bytes(), store.num_chunks
        store.demote(refs[:2])
        assert store.location(refs[0].digest) is not None  # remote-resident
        assert (store.stored_bytes(), store.num_chunks) == (before, n)
        bufs = [bytearray(r.size) for r in refs[:2]]
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs[:2], bufs)]
        )
        store.join_promotions()  # now resident in both pack tiers
        assert (store.stored_bytes(), store.num_chunks) == (before, n)

    def test_prefetch_lifts_ws_into_warm_tiers(self, tmp_path):
        rng = np.random.default_rng(3)
        payloads = _payloads(rng, 6, nzero=0)
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        refs = _fill(store, payloads)
        store.demote(refs[:3])
        stats = store.prefetch(refs)
        assert stats.remote_bytes == sum(r.size for r in refs[:3])
        assert stats.prefetched_chunks == len(refs)
        assert all(store.tier_of(r.digest) == "ram" for r in refs)
        # idempotent: everything already warm
        again = store.prefetch(refs)
        assert again.prefetched_chunks == 0
        assert again.already_warm == len(refs)


# ------------------------------------------------------- registry integration

def _tree(seed=0, n=3, rows=128, cols=32):
    rng = np.random.default_rng(seed)
    return {
        f"layer{i}": {
            "w": rng.standard_normal((rows, cols)).astype(np.float32),
            "b": rng.standard_normal((cols,)).astype(np.float32),
        }
        for i in range(n)
    }


def _registry(tmp_path, *, tiers=None):
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK, tiers=tiers)
    base_tree = _tree(seed=0)
    reg.register_runtime("fam", base_tree)
    variant = _tree(seed=0)
    variant["layer2"]["w"] = variant["layer2"]["w"] + 0.5
    variant["layer1"]["w"][:8] = 0.0
    variant["head"] = {"w": np.full((16, 16), 2.0, np.float32)}
    reg.register_function("fn", "fam", variant)
    log = AccessLog()
    for p in ("layer0/w", "layer0/b", "layer1/w", "layer2/w", "head/w"):
        log.touch(p)
    reg.generate_working_set("fn", log)
    return reg, variant


class TestRegistryTiered:
    def test_all_strategies_byte_identical_with_remote_residency(self, tmp_path):
        """Acceptance: the tiered store restores byte-identically across all
        five strategies even when the function's chunks live remote."""
        reg, variant = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        moved = reg.demote_function("fn")
        assert moved > 0
        flat = flatten_pytree(variant)
        src = lambda: {p: np.array(a) for p, a in flat.items()}
        kw = {
            "snapfaas": {},
            "snapfaas-": {},
            "reap": {},
            "seuss": dict(source_loader=src),
            "regular": dict(source_loader=src, base_loader=src),
        }
        for strategy, extra in kw.items():
            inst = reg.cold_start("fn", strategy, **extra)
            for path, expected in flat.items():
                np.testing.assert_array_equal(
                    inst.value(path), expected, err_msg=f"{strategy}/{path}"
                )
            reg.store.join_promotions()

    def test_promotion_never_double_counts_eager_bytes(self, tmp_path):
        """Acceptance: eager_bytes is the plan's eager set, restore after
        restore — promotion changes which tier serves it, never the count;
        the per-tier split always sums to it."""
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        reg.demote_function("fn")
        counts = []
        for _ in range(3):
            inst = reg.cold_start("fn", "snapfaas")
            m = inst.metrics
            assert sum(m.tier_bytes.values()) == m.eager_bytes
            counts.append((m.eager_bytes, m.eager_chunks))
            reg.store.join_promotions()
        assert len(set(counts)) == 1  # identical across promotions
        # by now promotion has drained the remote tier: served warm
        warm = reg.cold_start("fn", "snapfaas").metrics
        assert "remote" not in warm.tier_bytes

    def test_plan_split_refreshed_on_residency_change(self, tmp_path):
        """Tier movement refreshes a cached plan's placement in place —
        classification is residency-independent, so the plan itself (the
        expensive part) is never rebuilt."""
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        plan1 = reg.restore_plan("fn", "snapfaas")
        assert set(plan1.tier_split) == {"local"} or "ram" in plan1.tier_split
        arrays1 = plan1.arrays
        reg.demote_function("fn")
        plan2 = reg.restore_plan("fn", "snapfaas")
        assert plan2 is plan1 and plan2.arrays is arrays1  # not rebuilt
        assert "remote" in plan2.tier_split                # but re-placed
        assert plan2.residency_epoch == reg.store.residency_epoch

    def test_sizes_reports_tier_splits(self, tmp_path):
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        sizes = reg.sizes("fn")
        assert set(sizes.tier_splits) == {"full", "diff", "ws", "ws_full"}
        assert sum(sizes.tier_splits["ws"].values()) == sizes.ws_bytes
        reg.demote_function("fn")
        sizes2 = reg.sizes("fn")
        assert sizes2.tier_splits["ws"].get("remote", 0) > 0


# -------------------------------------------------------------- planner model

class TestTieredPlannerModel:
    HW = TieredStorageModel(
        name="t", bw_store=1e9, lat_store=1e-4,
        bw_mem=50e9, lat_mem=1e-7, bw_dma=30e9, preconfig=1e-3,
        tiers=(
            TierModel(name="ram", bw_store=50e9, lat_store=1e-6),
            TierModel(name="local", bw_store=1e9, lat_store=1e-4),
            TierModel(name="remote", bw_store=100e6, lat_store=5e-3),
        ),
    )

    def test_eager_time_is_max_of_pipelined_streams(self):
        split = {"ram": 10 << 20, "local": 10 << 20, "remote": 10 << 20}
        t = self.HW.eager_time(30 << 20, split=split)
        # pipelined: the remote stream dominates, the others hide under it
        remote_only = 5e-3 + (10 << 20) / 100e6
        assert t == pytest.approx(remote_only)

    def test_unsplit_bytes_fall_back_to_flat_constants(self):
        t = self.HW.eager_time(10 << 20, split={"ram": 1 << 20})
        flat = 1e-4 + (9 << 20) / 1e9
        assert t == pytest.approx(max(flat, 1e-6 + (1 << 20) / 50e9))

    def test_no_split_matches_flat_model(self):
        flat = StorageModel(
            name="f", bw_store=1e9, lat_store=1e-4,
            bw_mem=50e9, lat_mem=1e-7, bw_dma=30e9, preconfig=1e-3,
        )
        assert self.HW.eager_time(123456) == flat.eager_time(123456)

    def test_predict_prices_residency(self, tmp_path):
        """The same function predicts a slower B when its working set is
        remote-resident than when it is warm — Eq. 1 from the actual split."""
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        warm = predict("snapfaas", reg.sizes("fn"), self.HW)
        reg.demote_function("fn")
        cold = predict("snapfaas", reg.sizes("fn"), self.HW)
        assert cold.B > warm.B
        assert cold.total > warm.total


# ------------------------------------------------------------- serving layer

class TestServingTiers:
    @pytest.fixture()
    def worker(self, tmp_path):
        jax = pytest.importorskip("jax")
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.serving.worker import FunctionSpec, Worker

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        worker = Worker(
            str(tmp_path / "w"), chunk_bytes=4096,
            tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE),
        )
        base_params = model.init(0)
        worker.register_runtime("t", model, base_params)
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        variant = {k: np.array(v) for k, v in flat.items()}
        for k in variant:
            if k.endswith("wq"):
                variant[k] = variant[k] + 0.01
        worker.register_function(FunctionSpec(name="fn", family="t",
                                              variant=variant))
        return worker

    def test_register_prefetches_working_set(self, worker):
        stats = worker.tier_stats()
        assert stats["prefetched_bytes"] > 0
        assert stats["ram"]["used_bytes"] > 0

    def test_prefetch_hint_and_invoke(self, worker):
        import numpy as np

        from repro.serving import ColdStartOptions, InvocationRequest, Strategy

        worker.registry.demote_function("fn")
        worker.registry.store.drop_page_cache()  # clears the RAM tier too
        toks = np.zeros((1, 4), np.int32)
        r = worker.invoke(InvocationRequest(
            function="fn", tokens=toks,
            options=ColdStartOptions(strategy=Strategy.SNAPFAAS,
                                     force_cold=True, prefetch=True),
        ))
        assert r.cold
        # the prefetch hint promoted the WS before the timed boot: the
        # eager read never touched the remote tier
        assert "remote" not in r.metrics.tier_bytes
        assert worker.tier_stats()["prefetched_bytes"] > 0

    def test_cluster_metrics_expose_tier_outcomes(self, tmp_path):
        jax = pytest.importorskip("jax")
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.serving.cluster import Cluster
        from repro.serving.worker import FunctionSpec

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        with Cluster(
            str(tmp_path / "c"), n_workers=1, chunk_bytes=4096,
            tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE),
        ) as cluster:
            base_params = model.init(0)
            cluster.register_runtime("t", model, base_params)
            flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
            variant = {k: np.array(v) for k, v in flat.items()}
            variant["embed/table"] = variant["embed/table"] + 0.01
            cluster.register_function(FunctionSpec(name="fn", family="t",
                                                   variant=variant))
            m = cluster.metrics()
        tiers = m["tiers"]
        for key in ("ram_hits", "promoted_bytes", "prefetched_bytes",
                    "remote_fetch_s", "remote_fetched_bytes",
                    "prefetch_fetch_s"):
            assert key in tiers, key
        assert tiers["prefetched_bytes"] > 0
        assert m["per_worker"][0]["tiers"]["ram"]["used_bytes"] > 0
