"""Digest verification inside the read streams: each chunk is hashed as its
run lands, on the thread that read it, and the pass after the read only
compares, hashes what no stream hashed, and repairs.  The fault injector
corrupts a run before its hash, so a flip is hashed as flipped and repaired;
callers that ask for no verification hash nothing."""

import gc

import numpy as np
import pytest

import repro.core.chunkstore as chunkstore
import repro.core.tiers as tiers
from repro.core import (
    AccessLog,
    ChunkStore,
    FaultInjector,
    FaultMatrix,
    RetryPolicy,
    TieredChunkStore,
    TierSpec,
    ZygoteRegistry,
    execute_restore_plan,
    flatten_pytree,
)
from repro.core.faults import Corruption
from repro.core.restore import BasePool
from repro.core.tiers import TierReadStats

CHUNK = 4096
FAST = dict(remote_bw=10e9, remote_lat=0.0,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.0005,
                              max_delay_s=0.002, deadline_s=5.0))


def _payloads(seed, n, size):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, size, dtype=np.uint8).tobytes() for _ in range(n)]


def _fill(store, payloads, pack_id="p0"):
    pack = store.open_pack(pack_id)
    refs = store.put_chunks(pack, payloads)
    pack.close()
    store.save_index()
    return refs


@pytest.fixture
def post_pass(monkeypatch):
    """The chunks the pass after the read hashes itself."""
    hashed = []
    real = tiers.digest_many

    def digest_many(payloads):
        hashed.extend(payloads)
        return real(payloads)

    monkeypatch.setattr(tiers, "digest_many", digest_many)
    return hashed


@pytest.fixture
def digests_taken(monkeypatch):
    """Every BLAKE2b the store computes, wherever it is called from."""
    calls = []
    real = chunkstore.chunk_digest

    def chunk_digest(data):
        calls.append(len(data))
        return real(data)

    monkeypatch.setattr(chunkstore, "chunk_digest", chunk_digest)
    monkeypatch.setattr(tiers, "chunk_digest", chunk_digest)
    return calls


class _FlipMiddle(FaultInjector):
    """Flips one byte of the chunk in the middle (by pack offset) of every
    local read of three chunks or more: a chunk inside a coalesced run."""

    def __init__(self):
        super().__init__(FaultMatrix(tiers=("local",)))
        self.store = None
        self.flipped = []

    def corruption(self, tier, items):
        if tier != "local" or len(items) < 3:
            return super().corruption(tier, items)
        order = sorted(range(len(items)),
                       key=lambda k: self.store.location(items[k][0].digest).offset)
        k = order[len(order) // 2]
        self.flipped.append(items[k][0].digest)
        return Corruption(tier, items, {k: [len(items[k][1]) // 3]}, self._count)


# ------------------------------------------------ the scatter-read's run hook

def test_each_run_reports_its_chunks_once_as_they_land(tmp_path):
    """Across packs, split runs and repeated digests, the hook sees every
    distinct chunk exactly once, already holding its payload, and the
    destinations end byte-identical to a read without a hook."""
    store = ChunkStore(str(tmp_path / "s"))
    a = _payloads(1, 24, 256 * 1024)             # 6 MiB: split into two runs
    b = _payloads(2, 5, 3000)
    refs = _fill(store, a, "pa") + _fill(store, b, "pb")
    order = refs + refs[:3]                       # three repeated digests
    seen = {}

    def on_run(landed):
        for ref, view in landed:
            assert ref.digest not in seen
            seen[ref.digest] = bytes(view)

    bufs = [bytearray(r.size) for r in order]
    plain = [bytearray(r.size) for r in order]
    n = store.read_batch_into([(r, memoryview(x)) for r, x in zip(order, bufs)],
                              on_run=on_run)
    assert n == store.read_batch_into(
        [(r, memoryview(x)) for r, x in zip(order, plain)])
    assert seen == {r.digest: p for r, p in zip(refs, a + b)}
    assert bufs == plain == [bytearray(p) for p in (a + b + a[:3])]


# ------------------------------------------------------------ flips, repaired

@pytest.mark.parametrize("rows", ["fresh", "recycled"])
def test_flip_inside_a_coalesced_run_is_hashed_as_flipped(tmp_path, rows, post_pass):
    """A flip injected into one chunk in the middle of a coalesced run lands
    before that run is hashed: the stream's digest catches it, the chunk is
    repaired byte-identical, into fresh rows and into recycled ones, and
    the pass after the read hashes nothing itself."""
    inj = _FlipMiddle()
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK,
                         tiers=TierSpec(ram_bytes=0, faults=inj, **FAST))
    inj.store = reg.store.local
    rng = np.random.default_rng(8)
    base = {f"layer{i}": {"w": rng.standard_normal((100, 30)).astype(np.float32)}
            for i in range(2)}
    reg.register_runtime("fam", base)
    variant = {k: {"w": v["w"] + 0.5} for k, v in base.items()}
    reg.register_function("fn", "fam", variant)
    if rows == "recycled":
        first = reg.cold_start("fn", "snapfaas-")
        for ma in first.arrays.values():
            ma.patch.rows[:] = 0x5A
        del first, ma
        gc.collect()
    inj.flipped.clear()
    post_pass.clear()
    inst = reg.cold_start("fn", "snapfaas-")
    m = inst.metrics
    assert len(inj.flipped) == 1 and m.eager_chunks >= 3
    assert (m.recycled_bytes > 0) == (rows == "recycled")
    assert m.repaired_chunks == 1
    assert m.inline_verified_bytes == m.eager_bytes > 0
    assert post_pass == []
    for p, a in flatten_pytree(variant).items():
        np.testing.assert_array_equal(inst.value(p), a, err_msg=p)


# ------------------------------------------------------- the fault-free path

def test_fault_free_snapfaas_restore_is_verified_inside_its_streams(tmp_path, post_pass):
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
    rng = np.random.default_rng(3)
    base = {f"l{i}": {"w": rng.standard_normal((64, 32)).astype(np.float32)}
            for i in range(3)}
    reg.register_runtime("fam", base)
    variant = {k: {"w": v["w"] * 2.0} for k, v in base.items()}
    reg.register_function("fn", "fam", variant)
    log = AccessLog()
    for p in flatten_pytree(variant):
        log.touch(p)
    reg.generate_working_set("fn", log)
    post_pass.clear()
    inst = reg.cold_start("fn", "snapfaas")
    m = inst.metrics
    assert m.eager_bytes > 0
    assert m.inline_verified_bytes == m.eager_bytes
    assert post_pass == []
    for p, a in flatten_pytree(variant).items():
        np.testing.assert_array_equal(inst.value(p), a, err_msg=p)


@pytest.mark.parametrize("where", ["local", "ram-serial", "ram-parallel", "remote"])
def test_every_stream_hashes_what_it_lands(tmp_path, where, post_pass):
    """Pack runs, RAM copies (one by one and fanned out) and remote chunks
    are each hashed by their own stream; repeated digests ride on the one
    digest of their first copy."""
    store = TieredChunkStore(str(tmp_path / "s"),
                             spec=TierSpec(ram_bytes=64 << 20, **FAST))
    size = 256 * 1024 if where == "ram-parallel" else 3000
    payloads = _payloads(5, 24, size)
    refs = _fill(store, payloads)
    if where.startswith("ram"):
        for r, p in zip(refs, payloads):
            store.ram.put(r.digest, p)
    elif where == "remote":
        store.demote(refs)
    order = refs + refs[:4]
    bufs = [bytearray(r.size) for r in order]
    stats = TierReadStats()
    store.read_batch_into([(r, memoryview(b)) for r, b in zip(order, bufs)],
                          stats=stats, promote=False)
    assert stats.tier_bytes == {where.split("-")[0]: 24 * size}
    assert stats.inline_verified_bytes == 28 * size
    assert post_pass == []
    assert bufs == [bytearray(p) for p in payloads + payloads[:4]]


# ------------------------------------------ callers that verify nothing hash nothing

def test_flat_store_restores_hash_nothing(tmp_path, digests_taken):
    """The flat store's restore (the restore engine and the base pool load
    over a plain ``ChunkStore``) computes no digest: only the tiered store
    verifies."""
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
    rng = np.random.default_rng(4)
    base = {f"l{i}": {"w": rng.standard_normal((64, 32)).astype(np.float32)}
            for i in range(2)}
    reg.register_runtime("fam", base)
    variant = {k: {"w": v["w"] - 1.0} for k, v in base.items()}
    reg.register_function("fn", "fam", variant)
    reg.cold_start("fn", "snapfaas-")
    plan = reg.functions["fn"].plans["snapfaas-"]
    flat = reg.store.local
    digests_taken.clear()
    pool = BasePool.load(flat, reg.bases["fam"])
    planned = execute_restore_plan(plan, flat, pool)
    assert digests_taken == []
    for p, a in flatten_pytree(variant).items():
        np.testing.assert_array_equal(planned.value(p), a, err_msg=p)


def test_tiered_store_without_verify_reads_hashes_nothing(tmp_path, digests_taken):
    store = TieredChunkStore(str(tmp_path / "s"),
                             spec=TierSpec(ram_bytes=1 << 20, verify_reads=False,
                                           **FAST))
    payloads = _payloads(6, 16, 3000)
    refs = _fill(store, payloads)
    for r, p in zip(refs[:4], payloads):
        store.ram.put(r.digest, p)
    store.demote(refs[-4:])
    digests_taken.clear()
    bufs = [bytearray(r.size) for r in refs]
    stats = TierReadStats()
    store.read_batch_into([(r, memoryview(b)) for r, b in zip(refs, bufs)],
                          stats=stats, promote=False)
    assert digests_taken == []
    assert stats.inline_verified_bytes == 0
    assert bufs == [bytearray(p) for p in payloads]


# ------------------------------------------------------- fallback re-dispatch

@pytest.mark.parametrize("fallback", ["reclassified", "healed"])
def test_a_fallback_read_still_ends_verified(tmp_path, fallback, monkeypatch):
    """Every local chunk is flipped in flight.  When the local read must be
    re-dispatched through the hierarchy (an index race) or its retries run
    out (each chunk healed on its own), every chunk still ends verified and
    byte-identical."""
    matrix = FaultMatrix(seed=9, bit_flip=1.0, tiers=("local",))
    if fallback == "healed":
        matrix = FaultMatrix(seed=9, bit_flip=1.0, transient_ioerror=1.0,
                             tiers=("local",))
    store = TieredChunkStore(str(tmp_path / "s"),
                             spec=TierSpec(ram_bytes=0, faults=FaultInjector(matrix),
                                           **FAST))
    payloads = _payloads(7, 12, 3000)
    refs = _fill(store, payloads)
    # a shared base holds a good copy of each chunk, as the registry's pool does
    good = {r.digest: p for r, p in zip(refs, payloads)}
    store.add_fallback_source(lambda ref: good.get(ref.digest))
    if fallback == "reclassified":
        real = store._local_read
        raced = []

        def local_read(items, **kw):
            if not raced:
                raced.append(True)
                raise KeyError(items[0][0].digest)
            return real(items, **kw)

        monkeypatch.setattr(store, "_local_read", local_read)
    bufs = [bytearray(r.size) for r in refs]
    stats = TierReadStats()
    store.read_batch_into([(r, memoryview(b)) for r, b in zip(refs, bufs)],
                          stats=stats)
    assert bufs == [bytearray(p) for p in payloads]
    assert stats.repaired_chunks == len(refs)
    if fallback == "reclassified":
        assert stats.verify_failures == len(refs)
        assert stats.inline_verified_bytes == sum(r.size for r in refs)
