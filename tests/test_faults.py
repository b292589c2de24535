"""Fault injection and self-healing restore: retry/backoff, per-tier
circuit breaking, digest verification with quarantine-and-repair, hedged
remote fetches, worker-crash failover, and the chaos soak (``-m soak``).

The deterministic half (seeded :class:`FaultInjector`) makes the chaotic
half replayable: a failing run's (matrix, seed) reproduces the exact fault
sequence.  The acceptance invariant throughout is *never wrong bytes* —
every read either returns the payload that was stored or raises a typed
error from the failure taxonomy."""

import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    CHAOS_PROFILES,
    AccessLog,
    ChunkRef,
    CircuitBreaker,
    FaultError,
    FaultInjector,
    FaultMatrix,
    RetryPolicy,
    TieredChunkStore,
    TierReadError,
    TierSpec,
    TierUnavailableError,
    ZygoteRegistry,
    chaos_profile,
    flatten_pytree,
)
from repro.core.planner import TPU_TIERED
from repro.core.tiers import TierReadStats

CHUNK = 4096

# fast remote throttle: semantics, not timing
FAST_REMOTE = dict(remote_bw=10e9, remote_lat=0.0)
# fast backoff so retry-heavy tests stay in the millisecond range
FAST_RETRY = RetryPolicy(max_attempts=3, base_delay_s=0.0005,
                         max_delay_s=0.002, deadline_s=5.0)


def _payloads(rng, n, max_size=2 * CHUNK):
    return [rng.integers(0, 255, int(rng.integers(512, max_size)),
                         dtype=np.uint8).tobytes()
            for _ in range(n)]


def _fill(store, payloads, pack_id="p0"):
    pack = store.open_pack(pack_id)
    refs = store.put_chunks(pack, payloads)
    pack.close()
    store.save_index()
    return refs


class _Clock:
    """Hand-advanced clock for breaker / outage-window tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _FailFirst(FaultInjector):
    """Scripted injector: the first ``n`` reads fail with IOError, every
    later read passes clean — the deterministic transient-fault shape."""

    def __init__(self, n: int):
        super().__init__(FaultMatrix())
        self._budget = n

    def before_read(self, tier, items):
        with self._lock:
            if self._budget > 0:
                self._budget -= 1
                raise IOError("scripted transient fault")


# ------------------------------------------------------------ retry policy

class TestRetryPolicy:
    def test_backoff_grows_exponentially_and_caps(self):
        p = RetryPolicy(base_delay_s=0.01, max_delay_s=0.04, jitter=0.0)
        assert p.backoff_s(0) == pytest.approx(0.01)
        assert p.backoff_s(1) == pytest.approx(0.02)
        assert p.backoff_s(2) == pytest.approx(0.04)
        assert p.backoff_s(5) == pytest.approx(0.04)   # capped

    def test_jitter_stays_within_band(self):
        p = RetryPolicy(base_delay_s=0.01, jitter=0.5)
        rng = np.random.default_rng(0)
        for attempt in range(4):
            base = RetryPolicy(base_delay_s=0.01, jitter=0.0).backoff_s(attempt)
            for _ in range(50):
                d = p.backoff_s(attempt, rng)
                assert 0.5 * base <= d <= 1.5 * base

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(deadline_s=0.0)


# --------------------------------------------------------- circuit breaker

class TestCircuitBreaker:
    def test_opens_after_threshold_and_fails_fast(self):
        clk = _Clock()
        b = CircuitBreaker("remote", failure_threshold=3, reset_after_s=1.0,
                           clock=clk)
        for _ in range(2):
            b.record_failure()
            assert b.allow()
        b.record_failure()
        assert b.state == CircuitBreaker.OPEN
        assert not b.allow() and not b.allow()
        assert b.stats()["fail_fast"] == 2
        assert b.stats()["opens"] == 1

    def test_half_open_admits_exactly_one_probe(self):
        clk = _Clock()
        b = CircuitBreaker("remote", failure_threshold=1, reset_after_s=1.0,
                           clock=clk)
        b.record_failure()
        assert not b.allow()
        clk.t = 1.5
        assert b.state == CircuitBreaker.HALF_OPEN
        assert b.allow()            # the single probe
        assert not b.allow()        # everyone else keeps failing fast
        b.record_success()
        assert b.state == CircuitBreaker.CLOSED
        assert b.allow() and b.allow()

    def test_failed_probe_restarts_cooldown(self):
        clk = _Clock()
        b = CircuitBreaker("remote", failure_threshold=1, reset_after_s=1.0,
                           clock=clk)
        b.record_failure()
        clk.t = 1.5
        assert b.allow()
        b.record_failure()          # probe failed: cooldown restarts at 1.5
        assert not b.allow()
        clk.t = 2.0                 # only 0.5 s into the new cooldown
        assert not b.allow()
        clk.t = 2.6
        assert b.allow()

    def test_state_change_callback_fires_on_transitions(self):
        clk = _Clock()
        events = []
        b = CircuitBreaker("remote", failure_threshold=1, reset_after_s=1.0,
                           clock=clk,
                           on_state_change=lambda n, s: events.append((n, s)))
        b.record_failure()
        clk.t = 1.5
        assert b.allow()
        b.record_success()
        assert events == [("remote", "open"), ("remote", "closed")]


# ----------------------------------------------------------- fault injector

class TestFaultInjector:
    def test_same_seed_replays_the_same_fault_sequence(self):
        matrix = FaultMatrix(seed=7, transient_ioerror=0.3)

        def sequence():
            inj = FaultInjector(matrix)
            fired = []
            for _ in range(64):
                try:
                    inj.before_read("local", [])
                    fired.append(False)
                except IOError:
                    fired.append(True)
            return fired, inj.counters_snapshot()

        a, ca = sequence()
        b, cb = sequence()
        assert a == b and any(a) and not all(a)
        assert ca == cb

    def test_outage_window_follows_the_clock(self):
        clk = _Clock()
        inj = FaultInjector(FaultMatrix(remote_outage=(1.0, 2.0)), clock=clk)
        ref = ChunkRef(digest="ab" * 16, size=8)
        assert not inj.tier_down("remote")
        clk.t = 1.5
        assert inj.tier_down("remote")
        with pytest.raises(TierUnavailableError) as exc:
            inj.before_read("remote", [(ref, None)])
        assert exc.value.tier == "remote"
        assert exc.value.digests == [ref.digest]
        clk.t = 2.5
        assert not inj.tier_down("remote")

    def test_reset_clock_rearms_the_outage_window(self):
        clk = _Clock()
        inj = FaultInjector(FaultMatrix(remote_outage=(1.0, 2.0)), clock=clk)
        clk.t = 5.0  # window long expired (e.g. spent on registration)
        assert not inj.tier_down("remote")
        inj.reset_clock()
        assert not inj.tier_down("remote")  # window counts from t=5 now
        clk.t = 6.5
        assert inj.tier_down("remote")
        clk.t = 7.5
        assert not inj.tier_down("remote")

    def test_manual_fail_and_heal(self):
        inj = FaultInjector()
        assert not inj.tier_down("local")
        inj.fail_tier("local")
        assert inj.tier_down("local")
        assert inj.counters_snapshot()["tiers_down"] == ["local"]
        inj.heal_tier("local")
        assert not inj.tier_down("local")

    def test_chaos_profiles(self):
        for name in CHAOS_PROFILES:
            assert isinstance(chaos_profile(name, seed=3), FaultMatrix)
        assert chaos_profile("standard").crash_after is not None
        assert chaos_profile("remote-outage").remote_outage is not None
        with pytest.raises(ValueError):
            chaos_profile("nope")


# ------------------------------------------------- transient-fault recovery

class TestTransientRecovery:
    def _store(self, tmp_path, injector, **spec_kw):
        return TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(ram_bytes=0, faults=injector, retry=FAST_RETRY,
                          **FAST_REMOTE, **spec_kw),
        )

    def test_batch_read_survives_transient_local_faults(self, tmp_path):
        store = self._store(tmp_path, _FailFirst(2))
        payloads = _payloads(np.random.default_rng(0), 6)
        refs = _fill(store, payloads)
        bufs = [bytearray(r.size) for r in refs]
        stats = TierReadStats()
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs)], stats=stats
        )
        for b, p in zip(bufs, payloads):
            assert bytes(b) == p
        health = store.tier_stats()["health"]
        assert health["read_retries"] == 2
        assert stats.retries == 2
        # recovered, not degraded: the breaker reset on the success
        assert health["breakers"]["local"]["state"] == "closed"

    def test_get_chunk_retries_transient_fault(self, tmp_path):
        store = self._store(tmp_path, _FailFirst(1))
        [payload] = _payloads(np.random.default_rng(1), 1)
        [ref] = _fill(store, [payload])
        assert store.get_chunk(ref) == payload
        assert store.tier_stats()["health"]["read_retries"] == 1

    def test_exhausted_retries_surface_typed_error(self, tmp_path):
        store = self._store(tmp_path, _FailFirst(10 ** 6))
        refs = _fill(store, _payloads(np.random.default_rng(2), 3))
        bufs = [bytearray(r.size) for r in refs]
        with pytest.raises(TierReadError) as exc:
            store.read_batch_into(
                [(r, memoryview(b)) for r, b in zip(refs, bufs)]
            )
        # typed: the error names the chunk, the tier and the cause — never
        # a bare IOError/KeyError
        assert exc.value.tier == "local"
        assert exc.value.digests
        assert not isinstance(exc.value, (KeyError,))


# --------------------------------------- outage, breaker, AUTO re-pricing

class TestOutageAndBreaker:
    def _down_store(self, tmp_path):
        inj = FaultInjector()
        store = TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(ram_bytes=0, faults=inj, retry=FAST_RETRY,
                          **FAST_REMOTE),
        )
        payloads = _payloads(np.random.default_rng(3), 5)
        refs = _fill(store, payloads)
        store.demote(refs)
        return store, refs, payloads, inj

    def test_outage_opens_breaker_then_fails_fast_typed(self, tmp_path):
        store, refs, _payloads_, inj = self._down_store(tmp_path)
        inj.fail_tier("remote")

        def read_all():
            bufs = [bytearray(r.size) for r in refs]
            store.read_batch_into(
                [(r, memoryview(b)) for r, b in zip(refs, bufs)]
            )
            return bufs

        # enough failed attempts to cross the breaker threshold; every
        # failure is typed — never a bare IOError the caller can't classify
        for _ in range(3):
            with pytest.raises(TierReadError) as exc:
                read_all()
            assert exc.value.tier == "remote"
        breaker = store.breakers["remote"]
        assert breaker.is_open
        with pytest.raises(TierReadError):
            read_all()          # fail fast: no read reaches the dead tier
        health = store.tier_stats()["health"]
        assert health["fail_fast_reads"] > 0
        # an open remote breaker re-prices residency for the planner
        assert "remote!down" in store.residency(refs)
        assert store.residency_epoch > 0

    def test_heal_closes_breaker_via_probe_and_reads_recover(self, tmp_path):
        store, refs, payloads, inj = self._down_store(tmp_path)
        inj.fail_tier("remote")
        for _ in range(4):
            with pytest.raises(TierReadError):
                store.get_chunk(refs[0])
        assert store.breakers["remote"].is_open
        inj.heal_tier("remote")
        time.sleep(store.breakers["remote"].reset_after_s + 0.05)
        # half-open: the next read is the probe; success closes the breaker
        for r, p in zip(refs, payloads):
            assert store.get_chunk(r) == p
        assert store.breakers["remote"].state == CircuitBreaker.CLOSED
        assert "remote!down" not in store.residency(refs)

    def test_planner_prices_down_tier_at_outage_penalty(self):
        n = 1 << 24
        healthy = TPU_TIERED.eager_time(n, split={"remote": n})
        down = TPU_TIERED.eager_time(n, split={"remote!down": n})
        assert down > healthy
        assert down >= TPU_TIERED.outage_penalty_s

    def test_open_breaker_steers_auto_away_from_remote(self, tmp_path):
        jax = pytest.importorskip("jax")
        from repro.core.snapshot import flatten_pytree
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.serving import Strategy
        from repro.serving.worker import FunctionSpec, Worker

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        worker = Worker(
            str(tmp_path / "w"), chunk_bytes=CHUNK, storage=TPU_TIERED,
            tiers=TierSpec(ram_bytes=0, **FAST_REMOTE),
        )
        base_params = model.init(0)
        worker.register_runtime("t", model, base_params)
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        variant = {k: np.array(v) + 0.01 for k, v in flat.items()}
        worker.register_function(FunctionSpec(name="fn", family="t",
                                              variant=variant))
        worker.registry.demote_function("fn")
        cost_healthy = worker.predicted_cost("fn", Strategy.SNAPFAAS)
        breaker = worker.registry.store.breakers["remote"]
        for _ in range(breaker.failure_threshold):
            breaker.record_failure()
        assert breaker.is_open
        # the transition bumped the residency epoch: AUTO's Eq. 1 table
        # re-derives and prices the eager remote read at the outage penalty
        cost_down = worker.predicted_cost("fn", Strategy.SNAPFAAS)
        assert cost_down >= TPU_TIERED.outage_penalty_s > cost_healthy
        # and AUTO degrades gracefully: it picks a strategy that boots from
        # source artifacts instead of streaming the dead tier
        assert worker.resolve_strategy("fn", Strategy.AUTO) in (
            Strategy.SEUSS, Strategy.REGULAR
        )


# --------------------------------------------- corruption: verify + repair

class TestBitFlipRepair:
    def _store(self, tmp_path, matrix):
        return TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(ram_bytes=0, faults=FaultInjector(matrix),
                          retry=FAST_RETRY, **FAST_REMOTE),
        )

    def test_every_inflight_bitflip_repaired_byte_identical(self, tmp_path):
        store = self._store(
            tmp_path, FaultMatrix(seed=1, bit_flip=1.0, tiers=("local",))
        )
        payloads = _payloads(np.random.default_rng(4), 8)
        refs = _fill(store, payloads)
        bufs = [bytearray(r.size) for r in refs]
        stats = TierReadStats()
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs)], stats=stats
        )
        for b, p in zip(bufs, payloads):
            assert bytes(b) == p        # corrupt reads were never served
        health = store.tier_stats()["health"]
        assert health["verify_failures"] >= len(refs)
        assert health["repaired_chunks"] >= len(refs)
        assert stats.repaired_chunks >= len(refs)
        # in-flight corruption: the at-rest copies are fine, nothing is
        # quarantined — the same tier repaired itself on re-read
        assert health["quarantined_chunks"] == 0

    def test_partial_reads_repaired_on_demand_path(self, tmp_path):
        store = self._store(
            tmp_path, FaultMatrix(seed=2, partial_read=1.0, tiers=("local",))
        )
        payloads = _payloads(np.random.default_rng(5), 4)
        refs = _fill(store, payloads)
        for r, p in zip(refs, payloads):
            assert store.get_chunk(r) == p
        assert store.tier_stats()["health"]["repaired_chunks"] > 0


    def test_bitflips_read_into_recycled_rows_are_repaired(self, tmp_path):
        """A recycled rows buffer holds the previous instance's bytes (here
        scribbled over); every chunk flipped in flight into it is caught and
        repaired in place, so verification checks what the read wrote, not
        what the buffer held."""
        reg = ZygoteRegistry(
            str(tmp_path / "reg"), chunk_bytes=CHUNK,
            tiers=TierSpec(ram_bytes=0, retry=FAST_RETRY, **FAST_REMOTE,
                           faults=FaultInjector(FaultMatrix(
                               seed=4, bit_flip=1.0, tiers=("local",)))),
        )
        rng = np.random.default_rng(8)
        base = {f"layer{i}": {"w": rng.standard_normal((100, 30)).astype(np.float32)}
                for i in range(2)}
        reg.register_runtime("fam", base)
        variant = {k: {"w": v["w"] + 0.5} for k, v in base.items()}
        reg.register_function("fn", "fam", variant)
        first = reg.cold_start("fn", "snapfaas-")
        for ma in first.arrays.values():
            ma.patch.rows[:] = 0x5A
        del first, ma
        inst = reg.cold_start("fn", "snapfaas-")
        m = inst.metrics
        assert m.recycled_bytes == m.eager_bytes > 0
        assert m.repaired_chunks == m.eager_chunks > 0
        for p, a in flatten_pytree(variant).items():
            np.testing.assert_array_equal(inst.value(p), a, err_msg=p)


# ---------------------------------------------------------- hedged fetches

class TestHedgedFetch:
    def test_hedge_fires_on_slow_remote_and_bytes_match(self, tmp_path):
        store = TieredChunkStore(
            str(tmp_path / "s"),
            spec=TierSpec(
                ram_bytes=0, remote_bw=2e6, remote_lat=0.0,
                retry=RetryPolicy(max_attempts=3, base_delay_s=0.0005,
                                  hedge_after_s=0.001),
            ),
        )
        payloads = _payloads(np.random.default_rng(6), 4,
                             max_size=4 * CHUNK)
        refs = _fill(store, payloads)
        store.demote(refs)
        bufs = [bytearray(r.size) for r in refs]
        store.read_batch_into(
            [(r, memoryview(b)) for r, b in zip(refs, bufs)], promote=False
        )
        for b, p in zip(bufs, payloads):
            assert bytes(b) == p
        assert store.tier_stats()["health"]["hedged_fetches"] >= 1


# --------------------------------------------- property: never wrong bytes

class TestFaultMatrixProperty:
    @settings(max_examples=6, deadline=None)
    @given(
        seed=st.integers(0, 10_000),
        bit_flip=st.sampled_from([0.0, 0.05, 0.25]),
        transient=st.sampled_from([0.0, 0.1]),
        outage=st.booleans(),
    )
    def test_reads_are_correct_or_typed_under_any_matrix(
        self, tmp_path_factory, seed, bit_flip, transient, outage
    ):
        """PROPERTY: under any fault matrix, every read either returns the
        exact stored payload or raises a typed :class:`FaultError` — wrong
        bytes are never served, and bare IOError/KeyError never escape."""
        tmp = tmp_path_factory.mktemp("chaos")
        rng = np.random.default_rng(seed)
        payloads = _payloads(rng, 24)
        matrix = FaultMatrix(
            seed=seed, bit_flip=bit_flip, transient_ioerror=transient,
            remote_outage=(0.0, 0.25) if outage else None,
        )
        store = TieredChunkStore(
            str(tmp / "s"),
            spec=TierSpec(ram_bytes=1 << 20, faults=FaultInjector(matrix),
                          retry=FAST_RETRY, **FAST_REMOTE),
        )
        refs = _fill(store, payloads)
        store.demote(refs[12:])

        for _round in range(2):     # second round hits warmed/promoted tiers
            bufs = [bytearray(r.size) for r in refs]
            try:
                store.read_batch_into(
                    [(r, memoryview(b)) for r, b in zip(refs, bufs)]
                )
            except FaultError:
                pass                # typed failure: allowed under faults
            else:
                for r, b, p in zip(refs, bufs, payloads):
                    assert bytes(b) == p, r.digest
            store.join_promotions()

        for r, p in zip(refs, payloads):
            try:
                got = store.get_chunk(r)
            except FaultError:
                continue
            assert got == p, r.digest
        store.close()


# ------------------------------------------- demand fault-ins under chaos

class TestDemandPagingFaults:
    """Demand-paged restores materialize lazily, so chunk faults surface at
    *execution* time — on the same verified-read path as eager restores.
    Under chaos a demand fault-in either repairs in place
    (``_recover_chunk``) or raises a typed :class:`FaultError`; it can
    never hand execution wrong bytes."""

    def _registry(self, tmp, matrix_or_injector):
        inj = matrix_or_injector if isinstance(matrix_or_injector, FaultInjector) \
            else FaultInjector(matrix_or_injector)
        reg = ZygoteRegistry(
            str(tmp / "reg"), chunk_bytes=CHUNK,
            tiers=TierSpec(ram_bytes=0, faults=inj,
                           retry=FAST_RETRY, **FAST_REMOTE),
        )
        rng = np.random.default_rng(7)
        base_tree = {
            f"layer{i}": {"w": rng.standard_normal((96, 32)).astype(np.float32)}
            for i in range(3)
        }
        reg.register_runtime("fam", base_tree)
        variant = {k: {"w": v["w"] + 0.5} for k, v in base_tree.items()}
        variant["head"] = {"w": rng.standard_normal((24, 16)).astype(np.float32)}
        reg.register_function("fn", "fam", variant)
        # a deliberately partial recording: only head/w is prefetched, every
        # other dirty chunk is a genuine demand fault under chaos
        log = AccessLog()
        log.touch("head/w")
        reg.record_access("fn", log)
        return reg, flatten_pytree(variant)

    def test_demand_fault_ins_repair_bitflips(self, tmp_path):
        """Every lazy fault-in under guaranteed in-flight corruption is
        detected, repaired, and served byte-identical."""
        reg, flat = self._registry(
            tmp_path, FaultMatrix(seed=3, bit_flip=1.0, tiers=("local",))
        )
        inst = reg.cold_start("fn", "snapfaas", demand_paged=True)
        tree = inst.pytree()
        inst.finalize_demand_paging()
        for p, a in flat.items():
            np.testing.assert_array_equal(tree[p], a, err_msg=p)
        health = reg.store.tier_stats()["health"]
        assert health["verify_failures"] > 0
        assert health["repaired_chunks"] > 0
        assert inst.metrics.demand_faults > 0  # faulted, repaired, exact

    @settings(max_examples=4, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_lossy_disk_demand_reads_exact_or_typed(self, tmp_path_factory,
                                                    seed):
        """PROPERTY: under the lossy-disk profile a demand-paged execution
        either reads exactly the registered bytes or raises typed."""
        tmp = tmp_path_factory.mktemp("dp-lossy")
        reg, flat = self._registry(tmp, chaos_profile("lossy-disk", seed=seed))
        inst = reg.cold_start("fn", "snapfaas", demand_paged=True)
        try:
            tree = inst.pytree()
        except FaultError:
            return                  # typed failure: allowed under faults
        for p, a in flat.items():
            np.testing.assert_array_equal(tree[p], a, err_msg=p)

    def test_remote_outage_demand_faults_raise_typed(self, tmp_path):
        """Demoted chunks behind a dead remote: the demand-paged boot itself
        succeeds (nothing is streamed eagerly), and the execution-time
        fault-ins either raise typed or deliver exact bytes — never wrong
        ones."""
        inj = FaultInjector(chaos_profile("remote-outage", seed=5))
        reg, flat = self._registry(tmp_path, inj)
        reg.demote_function("fn")
        inst = reg.cold_start("fn", "snapfaas", demand_paged=True)
        assert inst.metrics.demand_paged     # boot completed under outage
        try:
            tree = inst.pytree()
        except FaultError:
            pass        # TierReadError/TierUnavailableError taxonomy
        else:
            for p, a in flat.items():
                np.testing.assert_array_equal(tree[p], a, err_msg=p)


# ------------------------------------------------- worker crash + failover

class TestWorkerFailover:
    def _build(self, root, *, faults=None):
        import jax
        from repro.core.snapshot import flatten_pytree
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
        cluster = Cluster(
            root, n_workers=2, chunk_bytes=CHUNK,
            tiers=TierSpec(ram_bytes=1 << 20, faults=faults, **FAST_REMOTE),
        )
        base_params = model.init(0)
        cluster.register_runtime("t", model, base_params)
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        specs = []
        for i in range(2):
            variant = {k: np.array(v) + 0.01 * (i + 1) for k, v in flat.items()}
            spec = FunctionSpec(name=f"fn{i}", family="t", variant=variant)
            cluster.register_function(spec)
            specs.append(spec)
        return cluster, specs

    def test_crashed_worker_fails_over_and_conserves_requests(self, tmp_path):
        pytest.importorskip("jax")
        from repro.serving import InvocationRequest

        inj = FaultInjector(FaultMatrix(crash_after=1))
        clean, specs = self._build(str(tmp_path / "clean"))
        chaos, _ = self._build(str(tmp_path / "chaos"), faults=inj)
        toks = np.arange(8, dtype=np.int32).reshape(1, 8) % 256
        with clean, chaos:
            expected = {
                s.name: clean.invoke(InvocationRequest(function=s.name,
                                                       tokens=toks)).output
                for s in specs
            }
            # the very first invocation crashes its worker; the cluster
            # detects it, re-shards onto the survivor, re-registers the
            # function there and re-dispatches — the request is not lost
            for s in specs:
                r = chaos.invoke(InvocationRequest(function=s.name,
                                                   tokens=toks))
                np.testing.assert_array_equal(np.asarray(r.output),
                                              np.asarray(expected[s.name]))
            m = chaos.metrics()
            assert m["serving"]["n_worker_crashes"] == 1
            assert len(m["serving"]["dead_workers"]) == 1
            dead = m["serving"]["dead_workers"][0]
            assert not m["per_worker"][dead]["alive"]
            # the failed-over request completed, flagged as recovered
            assert m["serving"]["failures"]["fault_recovered"] >= 1
            assert m["serving"]["failures"]["fault_fatal"] == 0
            assert m["chaos"]["worker_crash"] == 1
            # requests conserve: every submit completed despite the crash
            assert m["n_requests"] == len(specs)


# ----------------------------------------------------------- chaos soak

@pytest.mark.soak
def test_chaos_soak_conservation_and_byte_equivalence(tmp_path):
    """Short injected-fault soak: replay one trace through a clean fleet
    and a chaos fleet (bit flips + a worker crash mid-replay + a remote
    outage window).  Acceptance: request conservation holds, every error
    is typed, and every completed chaos result is byte-identical to the
    clean fleet's result for the same arrival."""
    pytest.importorskip("jax")
    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serving import make_trace
    from repro.serving.trace import build_cluster

    cfg = reduced(get_config("gemma-2b"))
    model = build_model(cfg)
    inj = FaultInjector(FaultMatrix(seed=5, bit_flip=0.02, crash_after=10))
    clean, clean_specs = build_cluster(
        str(tmp_path / "clean"), cfg, model, n_workers=2, n_functions=3,
        tiers=TierSpec(ram_bytes=32 << 20, **FAST_REMOTE),
    )
    chaos, chaos_specs = build_cluster(
        str(tmp_path / "chaos"), cfg, model, n_workers=2, n_functions=3,
        tiers=TierSpec(ram_bytes=32 << 20, faults=inj,
                       retry=FAST_RETRY, **FAST_REMOTE),
    )
    trace = make_trace("poisson", rps=120, duration_s=0.4, n_functions=3,
                       seed=11)
    with clean, chaos:
        clean_rep = clean.replay_trace(trace, clean_specs, time_scale=0)
        assert clean_rep.n_failed == 0 and clean_rep.n_shed == 0

        # cold-restore under faults: demote every function's chunks so the
        # outage window below actually bites, then open/close it mid-replay
        for s in chaos_specs:
            chaos.worker_for(s.name).registry.demote_function(s.name)
        down = threading.Timer(0.05, lambda: inj.fail_tier("remote"))
        heal = threading.Timer(0.30, lambda: inj.heal_tier("remote"))
        down.start(), heal.start()
        try:
            rep = chaos.replay_trace(trace, chaos_specs, time_scale=1.0)
        finally:
            down.cancel(), heal.cancel()
            inj.heal_tier("remote")

        # conservation: every arrival resolved to exactly one bucket
        assert rep.n_submitted == rep.n_completed + rep.n_shed + rep.n_failed
        assert rep.n_submitted == clean_rep.n_submitted
        # every failure is typed — never a bare IOError/KeyError
        for _i, exc in rep.errors:
            assert isinstance(exc, (FaultError, TimeoutError)), exc
        # zero byte-equivalence violations on everything that completed
        for got, want in zip(rep.results, clean_rep.results):
            if got is not None:
                np.testing.assert_array_equal(np.asarray(got.output),
                                              np.asarray(want.output))
        # one worker crashed mid-replay and the fleet kept serving
        m = chaos.metrics()
        assert m["serving"]["n_worker_crashes"] >= 1
        assert rep.n_completed > 0
        # the taxonomy sums are consistent with the report
        assert rep.failures()["shed"] == rep.n_shed
        assert rep.failures()["timeout"] + rep.failures()["fault_fatal"] \
            == rep.n_failed
