"""Regression tests for the concurrency bugs the soak harness shook out:
the plan-cache epoch check-then-act race in ``ZygoteRegistry``, the tier
lookup-then-read windows against concurrent demotion, and the RAM tier's
formerly-silent residency mutations."""

import threading

import numpy as np
import pytest

from repro.core import AccessLog, TieredChunkStore, TierSpec, ZygoteRegistry
from repro.core.tiers import RamCacheTier, TierReadStats

CHUNK = 4096
FAST_REMOTE = dict(remote_bw=10e9, remote_lat=0.0)


def _payloads(rng, n, size=6000):
    return [rng.integers(0, 255, size, dtype=np.uint8).tobytes()
            for _ in range(n)]


def _fill(store, payloads, pack_id="p0"):
    pack = store.open_pack(pack_id)
    refs = store.put_chunks(pack, payloads)
    pack.close()
    store.save_index()
    return refs


def _tree(seed=0, n=3):
    rng = np.random.default_rng(seed)
    return {
        f"layer{i}": {
            "w": rng.standard_normal((32, 32)).astype(np.float32),
            "b": rng.standard_normal((32,)).astype(np.float32),
        }
        for i in range(n)
    }


def _registry(tmp_path, *, tiers=None):
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK, tiers=tiers)
    base_tree = _tree(seed=0)
    reg.register_runtime("fam", base_tree)
    variant = _tree(seed=0)
    variant["layer2"]["w"] = variant["layer2"]["w"] + 0.5
    variant["head"] = {"w": np.full((16, 16), 2.0, np.float32)}
    reg.register_function("fn", "fam", variant)
    log = AccessLog()
    for p in ("layer0/w", "layer0/b", "layer1/w", "layer2/w", "head/w"):
        log.touch(p)
    reg.generate_working_set("fn", log)
    return reg, variant


class TestPlanEpochRace:
    def test_refresh_consistent_under_racing_demote(self, tmp_path):
        """Regression (ISSUE 5 satellite 1): hammer ``restore_plan`` from
        several threads while another thread demotes and prefetches the
        same function's chunks.  Every published (tier_split, epoch) pair
        must be internally consistent — the split always accounts for the
        full unique eager set — and once movement quiesces, the cached
        plan must converge to the store's actual residency instead of
        pinning a stale split under the newest epoch."""
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        plan0 = reg.restore_plan("fn", "snapfaas")
        unique = plan0.unique_eager_bytes
        stop = threading.Event()
        errors = []

        def refresher():
            try:
                while not stop.is_set():
                    plan = reg.restore_plan("fn", "snapfaas")
                    split = dict(plan.tier_split)  # atomic dict-ref read
                    assert set(split) <= {"ram", "local", "remote"}, split
                    assert sum(split.values()) == unique, split
            except Exception as e:  # noqa: BLE001 - surfaced after join
                errors.append(e)

        def mover():
            try:
                for _ in range(60):
                    if stop.is_set():
                        break
                    reg.demote_function("fn")
                    reg.prefetch_working_set("fn", "diff")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        threads = [threading.Thread(target=refresher) for _ in range(4)]
        threads.append(threading.Thread(target=mover))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors[:3]
        # quiesced: one more refresh must land exactly on reality — the
        # pinned-stale-split bug left this pair permanently inconsistent
        plan = reg.restore_plan("fn", "snapfaas")
        assert plan.residency_epoch == reg.store.residency_epoch
        assert plan.tier_split == reg.store.residency(plan.eager_refs())

    def test_build_stamps_epoch_before_residency(self, tmp_path):
        """A plan built while movement lands mid-``residency()`` must be
        stamped with the *pre-movement* epoch (so the next call refreshes)
        — never a post-movement epoch over pre-movement placement."""
        reg, _ = _registry(
            tmp_path, tiers=TierSpec(ram_bytes=1 << 20, **FAST_REMOTE)
        )
        store = reg.store
        orig_residency = store.residency
        fired = {}

        def racing_residency(refs):
            split = orig_residency(refs)
            if not fired:
                fired["x"] = True
                reg.demote_function("fn")  # movement during the pass
            return split

        store.residency = racing_residency
        try:
            plan = reg.restore_plan("fn", "snapfaas")
        finally:
            store.residency = orig_residency
        # the stale split is detectable: its epoch predates the movement
        assert plan.residency_epoch != store.residency_epoch
        plan2 = reg.restore_plan("fn", "snapfaas")
        assert plan2.tier_split == store.residency(plan2.eager_refs())


class TestTierLookupReadRaces:
    def test_get_chunk_survives_demote_between_lookup_and_read(self, tmp_path):
        """Regression (ISSUE 5 satellite 2): a demote landing between the
        local ``in`` check and the pack read must re-classify through the
        hierarchy and return the right bytes — not KeyError."""
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=0, **FAST_REMOTE)
        )
        payloads = _payloads(np.random.default_rng(0), 4)
        refs = _fill(store, payloads)
        victim = refs[2]
        orig = store.local.get_chunk
        fired = {}

        def racing(ref):
            if ref.digest == victim.digest and not fired:
                fired["x"] = True
                store.demote([victim])   # moves it remote mid-read
            return orig(ref)

        store.local.get_chunk = racing
        try:
            got = store.get_chunk(victim)
        finally:
            store.local.get_chunk = orig
        assert got == payloads[2]
        # the demote really fired mid-read (the chunk crossed to remote;
        # promote-on-fetch may have since copied it back down)
        assert fired and store.remote.has(victim.digest)

    def test_read_batch_survives_racing_demote(self, tmp_path):
        """Same window for the scatter-read: a demote landing between
        classification and the pack read makes the read raise KeyError, and
        the local sub-batch re-dispatches through the hierarchy."""
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=0, **FAST_REMOTE)
        )
        payloads = _payloads(np.random.default_rng(1), 4)
        refs = _fill(store, payloads)
        orig = store.pack.read_into
        calls = []

        def racing(items, **kw):
            calls.append(len(items))
            if len(calls) == 1:
                store.demote([refs[1]])   # classified local, now remote
            return orig(items, **kw)

        store.pack.read_into = racing
        bufs = [bytearray(r.size) for r in refs]
        stats = TierReadStats()
        try:
            store.read_batch_into(
                [(r, memoryview(b)) for r, b in zip(refs, bufs)], stats=stats
            )
        finally:
            store.pack.read_into = orig
        # the first pack read missed the demoted chunk; the fallback read
        # the rest locally and the demoted chunk from the remote tier
        assert calls == [4, 3]
        assert stats.tier_bytes == {"local": 3 * 6000, "remote": 6000}
        assert bufs == [bytearray(p) for p in payloads]

    def test_scatter_reads_byte_identical_under_movement_storm(self, tmp_path):
        """Sustained concurrent movement (demote/prefetch cycles) against
        looping scatter-reads: every read returns byte-identical content —
        a reader can never see a digest the residency snapshot claimed
        resident but the tier already evicted."""
        store = TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(ram_bytes=24_000, **FAST_REMOTE),
        )
        rng = np.random.default_rng(2)
        payloads = _payloads(rng, 10)
        refs = _fill(store, payloads)
        expected = {r.digest: p for r, p in zip(refs, payloads)}
        stop = threading.Event()
        errors = []

        def reader(seed):
            r = np.random.default_rng(seed)
            try:
                while not stop.is_set():
                    order = list(r.permutation(len(refs)))
                    batch = [refs[i] for i in order]
                    bufs = [bytearray(ref.size) for ref in batch]
                    stats = TierReadStats()
                    store.read_batch_into(
                        [(ref, memoryview(b)) for ref, b in zip(batch, bufs)],
                        stats=stats,
                    )
                    for ref, buf in zip(batch, bufs):
                        assert bytes(buf) == expected[ref.digest], ref.digest
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def mover():
            r = np.random.default_rng(99)
            try:
                for _ in range(40):
                    if stop.is_set():
                        break
                    pick = [refs[i] for i in r.permutation(len(refs))[:4]]
                    store.demote(pick)
                    store.prefetch(pick)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        threads = [threading.Thread(target=reader, args=(s,)) for s in range(3)]
        threads.append(threading.Thread(target=mover))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        store.join_promotions()
        assert not errors, errors[:3]


class TestRamResidencyAdvertised:
    def test_lru_eviction_bumps_epoch(self, tmp_path):
        """An LRU eviction is tier movement: it must bump the residency
        epoch so cached splits claiming the digest RAM-resident go stale
        (it used to be the one movement nothing advertised)."""
        store = TieredChunkStore(
            str(tmp_path / "s"), spec=TierSpec(ram_bytes=8_000)
        )
        payloads = _payloads(np.random.default_rng(3), 2)
        refs = _fill(store, payloads)
        store.prefetch([refs[0]])
        assert store.tier_of(refs[0].digest) == "ram"
        e0 = store.residency_epoch
        store.prefetch([refs[1]])      # capacity holds one: evicts refs[0]
        assert store.tier_of(refs[0].digest) == "local"
        assert store.residency_epoch > e0

    def test_ram_callback_fires_on_removals_outside_lock(self):
        """Removals (evicting put, discard, clear) fire the callback after
        the RAM lock drops (it may re-enter tier state — the store's epoch
        bump takes its own lock); plain insertions do NOT fire (per-insert
        bumps would invalidate every cached plan on every demand fault)."""
        tier = RamCacheTier(10)
        seen = []

        def cb():
            # would deadlock if invoked under tier._lock
            assert not tier._lock.locked()
            seen.append(tier.used)

        tier._on_change = cb
        tier.put("a", b"12345")    # plain insertion: silent
        assert seen == []
        tier.put("b", b"123456")   # evicts "a": fires
        assert len(seen) == 1
        tier.put("c", b"1234")     # fits alongside "b": silent
        assert len(seen) == 1
        tier.discard(["b"])        # fires
        tier.clear()               # "c" still resident: fires
        assert len(seen) == 3


# --------------------------------------------------------------------------
# regressions for the races the static analyzer (repro.analysis) surfaced


class TestCategoryRefsPublishRace:
    def test_stale_ws_refs_never_republished(self, tmp_path, monkeypatch):
        """Regression (guards pass, G001 on ``category_refs``): the old
        ``_category_refs`` computed lock-free and published under no lock,
        so a compute racing ``generate_working_set``'s swap-and-clear could
        re-publish refs cut from the dead working set — permanently, since
        nothing would ever invalidate them again.  Compute and publish now
        both run under ``plan_lock``; this pins the interleaving with a
        blocked ``resolve`` and asserts the WS swap (a) waits for the
        in-flight compute and (b) leaves the cache invalidated, not stale."""
        from repro.core import registry as registry_mod

        reg, _ = _registry(tmp_path)
        rec = reg.functions["fn"]

        entered = threading.Event()
        release = threading.Event()
        real_resolve = registry_mod.resolve
        # only the first resolve() after arming blocks — that is the
        # compute thread's call, because the swapper starts later
        armed = [True]

        def slow_resolve(*args, **kwargs):
            if armed and armed.pop():
                entered.set()
                assert release.wait(timeout=10)
            return real_resolve(*args, **kwargs)

        monkeypatch.setattr(registry_mod, "resolve", slow_resolve)

        stale_out = {}

        def compute():
            stale_out["refs"] = reg._category_refs("fn")

        computer = threading.Thread(target=compute)
        computer.start()
        assert entered.wait(timeout=10)

        # swap the working set down to a strict subset while the compute
        # is parked inside its critical section
        small_log = AccessLog()
        small_log.touch("layer0/w")
        swapper = threading.Thread(
            target=reg.generate_working_set, args=("fn", small_log)
        )
        swapper.start()
        # the swap's plan_lock section must wait for the in-flight compute
        swapper.join(timeout=0.4)
        assert swapper.is_alive(), (
            "generate_working_set finished while _category_refs was still "
            "inside its critical section: publish is not serialised"
        )

        release.set()
        computer.join(timeout=10)
        swapper.join(timeout=10)
        assert not computer.is_alive() and not swapper.is_alive()

        # the swap ran last: the stale publish must be gone
        with rec.plan_lock:
            assert rec.category_refs is None, (
                "stale category_refs survived the working-set swap"
            )
        fresh = reg._category_refs("fn")
        assert len(fresh["ws"]) < len(stale_out["refs"]["ws"]), (
            "fresh refs should reflect the shrunken working set"
        )


class TestTierCounterExactness:
    def test_concurrent_prefetch_counts_every_byte(self, tmp_path):
        """Regression (guards pass, G001 on the telemetry counters): the
        tier counters were bumped with plain ``+=`` — a racy
        read-modify-write that loses updates under concurrent prefetches.
        All counter mutations now take ``_stats_lock``; disjoint parallel
        prefetches must account for every byte exactly."""
        rng = np.random.default_rng(7)
        n_threads, per_thread = 8, 4
        payloads = _payloads(rng, n_threads * per_thread)
        store = TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(ram_bytes=64 << 20, **FAST_REMOTE),
        )
        refs = _fill(store, payloads)
        assert store.demote(refs) == sum(len(p) for p in payloads)

        slices = [
            refs[i * per_thread:(i + 1) * per_thread]
            for i in range(n_threads)
        ]
        barrier = threading.Barrier(n_threads)
        errors = []

        def prefetcher(chunk_refs):
            try:
                barrier.wait(timeout=10)
                store.prefetch(chunk_refs)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=prefetcher, args=(s,))
                   for s in slices]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        store.join_promotions()

        total = sum(r.size for r in refs)
        stats = store.tier_stats()
        assert stats["prefetched_bytes"] == total, (
            f"lost counter updates: {stats['prefetched_bytes']} != {total}"
        )
        assert all(store.tier_of(r.digest) == "ram" for r in refs)


class TestArenaRace:
    def test_no_block_is_handed_to_two_live_instances(self, tmp_path):
        """More threads than cores cold-start one function at a short switch
        interval, check the restored bytes, stamp every rows buffer with
        their own byte and read the stamp back: a block handed to two live
        instances at once would show another thread's byte."""
        import os
        import sys
        import time

        reg, variant = _registry(tmp_path)
        want = variant["layer2"]["w"]
        n = (os.cpu_count() or 4) + 4
        errors, counts = [], [0] * n
        stop = time.monotonic() + 2.0

        def work(i):
            try:
                while time.monotonic() < stop:
                    inst = reg.cold_start("fn", "snapfaas")
                    np.testing.assert_array_equal(inst.value("layer2/w"), want)
                    rows = [ma.patch.rows for ma in inst.arrays.values()
                            if ma.patch is not None]
                    for r in rows:
                        r[:] = i
                    time.sleep(0)
                    if not all(np.all(r == i) for r in rows):
                        errors.append(f"thread {i}: rows overwritten")
                    counts[i] += 1
                    del inst, rows
            except Exception as e:  # broad-ok: surfaced by the assert below
                errors.append(e)

        threads = [threading.Thread(target=work, args=(i,)) for i in range(n)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors[:3]
        assert all(counts)
        stats = reg.arena.stats()
        assert stats["hits"] > 0
        assert stats["free_bytes"] <= stats["cap"]
