"""Content-addressed store: cross-function dedup, shared-base registration,
refcounted GC, index-format migration and digest-collision rejection."""

import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AccessLog,
    ChunkIntegrityError,
    ChunkRef,
    ChunkStore,
    DigestCollisionError,
    INDEX_VERSION,
    IndexCorruptionError,
    TierSpec,
    ZygoteRegistry,
    flatten_pytree,
    manifest_digests,
    take_snapshot,
)
from repro.core.planner import PAPER_C220G5, TPU_TIERED

CHUNK = 4096


def _tree(seed=0, n=3, rows=64, cols=32):
    rng = np.random.default_rng(seed)
    return {
        f"l{i}": {"w": rng.standard_normal((rows, cols)).astype(np.float32)}
        for i in range(n)
    }


def _registry(tmp_path, name="reg"):
    reg = ZygoteRegistry(str(tmp_path / name), chunk_bytes=CHUNK)
    reg.register_runtime("fam", _tree(0))
    return reg


def _touch_all(reg, fn, extra=()):
    log = AccessLog()
    for p in list(flatten_pytree(_tree(0))) + list(extra):
        log.touch(p)
    reg.generate_working_set(fn, log)


def _loaders(full_flat, delta_paths):
    return dict(
        source_loader=lambda: {p: np.array(full_flat[p]) for p in delta_paths},
        base_loader=lambda: {p: np.array(a) for p, a in full_flat.items()},
    )


# ------------------------------------------------------------- collisions

class TestDigestCollision:
    def _seed_store(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        pack = store.open_pack("p0")
        payload = np.arange(256, dtype=np.uint8).tobytes()
        [ref] = store.put_chunks(pack, [payload])
        pack.close()
        store.save_index()
        return store, ref, payload

    def test_put_rejects_same_digest_different_length(self, tmp_path):
        store, ref, _ = self._seed_store(tmp_path)
        pack = store.open_pack("p1")
        bad = ChunkRef(digest=ref.digest, size=ref.size + 8)
        with pytest.raises(DigestCollisionError):
            store.put_chunks(pack, [b"\x01" * (ref.size + 8)], refs=[bad])

    def test_register_rejects_length_mismatch(self, tmp_path):
        from repro.core.chunkstore import ChunkLoc

        store, ref, _ = self._seed_store(tmp_path)
        with pytest.raises(DigestCollisionError):
            store.register_chunks(
                [(ref.digest, ChunkLoc(pack="px", offset=0, size=ref.size + 1))]
            )

    def test_read_paths_reject_length_mismatch(self, tmp_path):
        store, ref, _ = self._seed_store(tmp_path)
        bad = ChunkRef(digest=ref.digest, size=ref.size - 16)
        with pytest.raises(DigestCollisionError):
            store.get_chunk(bad)
        with pytest.raises(DigestCollisionError):
            store.read_batch_into([(bad, memoryview(bytearray(bad.size)))])

    def test_index_load_rejects_colliding_lengths(self, tmp_path):
        """Regression: a (v0) index aliasing one digest to two different
        lengths must fail loudly instead of silently serving the first."""
        root = tmp_path / "s"
        store = ChunkStore(str(root))
        store.close()
        v0 = {"functions": {
            "fnA": {"arr": [["p0", 0, 256, "d" * 32]]},
            "fnB": {"arr": [["p0", 512, 300, "d" * 32]]},
        }}
        with open(root / "index.json", "w") as f:
            json.dump(v0, f)
        with pytest.raises(DigestCollisionError):
            ChunkStore(str(root))


# -------------------------------------------------------- index migration

class TestIndexMigration:
    def _populated(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        rng = np.random.default_rng(0)
        payloads = [rng.integers(0, 255, 300 + i, dtype=np.uint8).tobytes()
                    for i in range(4)]
        pack = store.open_pack("p0")
        refs = store.put_chunks(pack, payloads)
        pack.close()
        store.save_index()
        store.close()
        return str(tmp_path / "s"), refs, payloads

    def test_v1_flat_map_auto_upgrades(self, tmp_path):
        root, refs, payloads = self._populated(tmp_path)
        with open(os.path.join(root, "index.json")) as f:
            v2 = json.load(f)
        assert v2["version"] == INDEX_VERSION
        # rewrite as the legacy v1 layout (bare digest map)
        with open(os.path.join(root, "index.json"), "w") as f:
            json.dump(v2["chunks"], f)
        store = ChunkStore(root)
        for ref, payload in zip(refs, payloads):
            assert store.get_chunk(ref) == payload
        store.save_index()          # persisting upgrades the on-disk layout
        with open(os.path.join(root, "index.json")) as f:
            again = json.load(f)
        assert again["version"] == INDEX_VERSION
        assert again["chunks"] == v2["chunks"]

    def test_v0_per_function_layout_auto_upgrades(self, tmp_path):
        root, refs, payloads = self._populated(tmp_path)
        with open(os.path.join(root, "index.json")) as f:
            v2 = json.load(f)
        # two functions naming overlapping digests at their pack offsets —
        # the pre-CAS layout keyed by (function, array, offset)
        rows = [[*v2["chunks"][r.digest], r.digest] for r in refs]
        v0 = {"functions": {
            "fnA": {"arr0": rows[:3]},
            "fnB": {"arr0": rows[1:]},
        }}
        with open(os.path.join(root, "index.json"), "w") as f:
            json.dump(v0, f)
        store = ChunkStore(root)
        for ref, payload in zip(refs, payloads):
            assert store.get_chunk(ref) == payload
        # duplicate digests across functions dedup into one entry, and the
        # upgrade seeds refcounts with the number of referencing functions
        assert store.num_chunks == len(refs)
        assert store.refcount(refs[0].digest) == 1
        assert store.refcount(refs[1].digest) == 2

    def test_newer_version_rejected(self, tmp_path):
        root, _, _ = self._populated(tmp_path)
        with open(os.path.join(root, "index.json"), "w") as f:
            json.dump({"version": INDEX_VERSION + 1, "chunks": {}}, f)
        with pytest.raises(IndexCorruptionError):
            ChunkStore(root)

    def test_refcounts_persist_and_repin_is_idempotent(self, tmp_path):
        root, refs, _ = self._populated(tmp_path)
        store = ChunkStore(root)
        store.pin([r.digest for r in refs[:2]], owner="fnA")
        store.pin([refs[0].digest], owner="fnB")
        store.save_index()
        store.close()
        again = ChunkStore(root)
        assert again.refcount(refs[0].digest) == 2
        assert again.refcount(refs[1].digest) == 1
        assert again.refcount(refs[2].digest) == 0
        # re-registering after a restart re-pins the same owners — counts
        # must NOT inflate, or deregister GC could never reach zero
        again.pin([r.digest for r in refs[:2]], owner="fnA")
        assert again.refcount(refs[0].digest) == 2
        assert again.unpin([refs[1].digest], owner="fnA") == [refs[1].digest]


# ------------------------------------------------- shared-base registration

class TestRegisterFromBase:
    def _variant(self):
        base = _tree(0)
        full = {k: {kk: np.array(vv) for kk, vv in v.items()}
                for k, v in base.items()}
        full["l2"]["w"] = full["l2"]["w"] + 0.5
        full["head"] = {"w": np.full((16, 16), 2.0, np.float32)}
        delta = {"l2/w": np.array(full["l2"]["w"]),
                 "head/w": np.array(full["head"]["w"])}
        return full, delta

    def test_all_strategies_byte_identical_to_full_registration(self, tmp_path):
        full, delta = self._variant()
        full_flat = flatten_pytree(full)

        reg_a = _registry(tmp_path, "a")
        reg_a.register_from_base("fn", "fam", delta)
        _touch_all(reg_a, "fn", extra=delta)

        reg_b = _registry(tmp_path, "b")
        reg_b.register_function("fn", "fam", full)
        _touch_all(reg_b, "fn", extra=delta)

        kw = _loaders(full_flat, set(delta))
        for strategy in ("regular", "reap", "seuss", "snapfaas-", "snapfaas"):
            extra = kw if strategy in ("seuss", "regular") else {}
            a = reg_a.cold_start("fn", strategy, **extra)
            b = reg_b.cold_start("fn", strategy, **extra)
            assert set(a.arrays) == set(b.arrays)
            for path in a.arrays:
                np.testing.assert_array_equal(
                    a.value(path), b.value(path), err_msg=f"{strategy}/{path}"
                )
                np.testing.assert_array_equal(
                    a.value(path), full_flat[path], err_msg=f"{strategy}/{path}"
                )

    def test_capture_writes_only_the_delta(self, tmp_path):
        _, delta = self._variant()
        reg = _registry(tmp_path)
        before = reg.store.stored_bytes()
        reg.register_from_base("fn", "fam", delta)
        written = reg.store.stored_bytes() - before
        delta_bytes = sum(a.nbytes for a in delta.values())
        assert 0 < written <= delta_bytes      # never the full snapshot
        assert written < before                # base is much bigger

    def test_duplicate_registration_rejected(self, tmp_path):
        _, delta = self._variant()
        reg = _registry(tmp_path)
        reg.register_from_base("fn", "fam", delta)
        with pytest.raises(ValueError):
            reg.register_from_base("fn", "fam", delta)


# ------------------------------------------------------------ refcounted GC

class TestDeregisterGC:
    def _two_functions(self, tmp_path):
        reg = _registry(tmp_path)
        base = _tree(0)
        shared_delta = {"l1/w": np.asarray(base["l1"]["w"]) + 1.0}
        reg.register_from_base("fnA", "fam", dict(shared_delta))
        # fnB shares fnA's delta chunk AND adds its own unique array
        own = {"own/w": np.full((32, 32), 3.0, np.float32)}
        reg.register_from_base("fnB", "fam", {**shared_delta, **own})
        return reg

    def test_shared_chunks_survive_deregister(self, tmp_path):
        reg = self._two_functions(tmp_path)
        base_digests = set(manifest_digests(reg.bases["fam"]))
        freed = reg.deregister_function("fnB")
        assert freed > 0                       # fnB's unique array went away
        assert "fnB" not in reg.functions
        # base and the shared delta chunk are still restorable through fnA
        inst = reg.cold_start("fnA", "snapfaas-")
        np.testing.assert_array_equal(
            inst.value("l1/w"), np.asarray(_tree(0)["l1"]["w"]) + 1.0
        )
        for d in base_digests:
            assert d in reg.store

    def test_fully_shared_function_frees_nothing(self, tmp_path):
        reg = self._two_functions(tmp_path)
        # fnA's chunks are all shared (base + fnB references the delta)
        assert reg.deregister_function("fnA") == 0
        inst = reg.cold_start("fnB", "snapfaas-")
        np.testing.assert_array_equal(
            inst.value("own/w"), np.full((32, 32), 3.0, np.float32)
        )

    def test_deregister_compact_reclaims_disk(self, tmp_path):
        reg = self._two_functions(tmp_path)
        pack_dir = os.path.join(reg.store.root, "packs")

        def disk():
            return sum(os.path.getsize(os.path.join(pack_dir, f))
                       for f in os.listdir(pack_dir))

        before = disk()
        freed = reg.deregister_function("fnB", compact=True)
        assert freed > 0
        assert disk() < before
        inst = reg.cold_start("fnA", "snapfaas-")   # survivors still restore
        np.testing.assert_array_equal(
            inst.value("l0/w"), _tree(0)["l0"]["w"]
        )

    def test_repeated_compaction_is_safe(self, tmp_path):
        """A second compact() must not overwrite the pack it is reading
        (streamed rewrite picks a fresh pack id)."""
        reg = self._two_functions(tmp_path)
        reg.store.compact()
        reg.store.compact()
        inst = reg.cold_start("fnB", "snapfaas-")
        np.testing.assert_array_equal(
            inst.value("own/w"), np.full((32, 32), 3.0, np.float32)
        )

    def test_reclaim_counts_dual_resident_chunks_once(self, tmp_path):
        """A chunk promoted into both pack tiers is ONE logical chunk —
        reclaim must not report its bytes twice."""
        reg = self._two_functions(tmp_path)
        store = reg.store
        rec = reg.functions["fnB"]
        refs = [c for a in rec.diff.arrays.values() for c in a.chunks
                if c is not None and not c.zero]
        # demote fnB's diff chunks, then prefetch them back: now resident
        # in BOTH the remote and local pack tiers
        store.demote(refs)
        store.prefetch(refs)
        dead = set(store.unpin(set(manifest_digests(rec.diff, rec.full)),
                               owner="fnB"))
        freed = store.reclaim(list(dead))
        dead_sizes = {c.digest: c.size for c in refs if c.digest in dead}
        assert dead_sizes                          # fnB's own array died
        assert freed == sum(dead_sizes.values())   # once, not twice

    def test_manifest_files_removed(self, tmp_path):
        reg = self._two_functions(tmp_path)
        man = os.path.join(reg.root, "manifests")
        assert os.path.exists(os.path.join(man, "diff-fnB.json"))
        reg.deregister_function("fnB")
        assert not os.path.exists(os.path.join(man, "diff-fnB.json"))
        with pytest.raises(KeyError):
            reg.deregister_function("fnB")

    def test_dedup_stats(self, tmp_path):
        reg = self._two_functions(tmp_path)
        s = reg.dedup_stats()
        assert s["functions"] == 2
        assert s["unique_bytes"] < s["referenced_bytes"]
        assert 0 < s["dedup_ratio"] < 1
        assert s["shared_digests"] > 0


# ------------------------------------------- dedup-aware planner inputs

class TestDedupPlanner:
    def test_shared_hit_discount_flat_model(self):
        hw = PAPER_C220G5
        full = hw.eager_time(1 << 24)
        half = hw.eager_time(1 << 24, shared_hit=0.5)
        warm = hw.eager_time(1 << 24, shared_hit=1.0)
        assert warm < half < full
        # fully warm leaves only the request latency + memcpy
        assert warm == pytest.approx(hw.lat_store + (1 << 24) / hw.bw_mem)

    def test_tiered_model_prefers_measured_split(self):
        # with a residency split the shared-hit discount must NOT double
        # count: the split already says where the bytes live
        n = 1 << 24
        t = TPU_TIERED.eager_time(n, split={"local": n}, shared_hit=1.0)
        assert t == TPU_TIERED.eager_time(n, split={"local": n})

    def test_sizes_reports_shared_ram_fraction(self, tmp_path):
        reg = _registry(tmp_path)
        base = _tree(0)
        delta = {"l1/w": np.asarray(base["l1"]["w"]) + 1.0}
        reg.register_from_base("fnA", "fam", dict(delta))
        reg.register_from_base("fnB", "fam", dict(delta))
        for fn in ("fnA", "fnB"):
            _touch_all(reg, fn, extra=delta)
        assert reg.sizes("fnA").shared_hit_fracs["full"] == 0.0
        # RAM-warm fnB's full set; fnA's shared fraction must light up —
        # residency is digest-keyed, one cached chunk serves both siblings
        reg.prefetch_working_set("fnB", category="full")
        fracs = reg.sizes("fnA").shared_hit_fracs
        assert fracs["full"] > 0.9


# ----------------------------------------------------- reopen / restart

class TestReopenSafety:
    def test_private_chunks_are_not_shared(self, tmp_path):
        """A function's own delta chunks appear in BOTH its diff and its
        synthesized full manifest — that is one function-reference, not
        two: a single-function store must report zero cross-function
        sharing for them."""
        reg = _registry(tmp_path)
        delta = {"head/w": np.full((16, 16), 2.0, np.float32)}
        reg.register_from_base("fn", "fam", delta)
        shared = reg.store.shared_digests()
        for d in manifest_digests(reg.functions["fn"].diff):
            assert reg.store.refcount(d) == 1
            assert d not in shared

    def test_reopen_and_reregister_preserves_payloads(self, tmp_path):
        """Restart flow: reopen the same store root and re-run the same
        registrations.  Packs must not be truncated (the persisted index
        still points into them) and refcounts must not inflate."""
        root = str(tmp_path / "reg")
        delta = {"head/w": np.full((16, 16), 2.0, np.float32)}

        def register(reg):
            reg.register_runtime("fam", _tree(0))
            reg.register_from_base("fn", "fam", dict(delta))
            _touch_all(reg, "fn", extra=delta)

        reg = ZygoteRegistry(root, chunk_bytes=CHUNK)
        register(reg)
        base_digest = manifest_digests(reg.bases["fam"])[0]
        count_before = reg.store.refcount(base_digest)
        reg.store.close()

        reg2 = ZygoteRegistry(root, chunk_bytes=CHUNK)
        register(reg2)
        inst = reg2.cold_start("fn", "snapfaas")
        np.testing.assert_array_equal(inst.value("l0/w"), _tree(0)["l0"]["w"])
        np.testing.assert_array_equal(inst.value("head/w"), delta["head/w"])
        assert reg2.store.refcount(base_digest) == count_before
        # and deregistration GC still works after the restart
        assert reg2.deregister_function("fn") > 0


# ------------------------------------------------- serving-level delta path

class TestServingDelta:
    def _worker_pair(self, tmp_path):
        import jax
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.serving.worker import FunctionSpec, Worker

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        base_params = model.init(0)
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        delta = {}
        for k in flat:
            if k.endswith("wq"):
                delta[k] = np.array(flat[k]) + 0.01
        variant = {k: np.array(v) for k, v in flat.items()}
        variant.update({k: np.array(v) for k, v in delta.items()})

        w_delta = Worker(str(tmp_path / "wd"), chunk_bytes=4096)
        w_delta.register_runtime("t", model, base_params)
        w_delta.register_function(FunctionSpec(name="fn", family="t",
                                               delta=delta))
        w_full = Worker(str(tmp_path / "wf"), chunk_bytes=4096)
        w_full.register_runtime("t", model, base_params)
        w_full.register_function(FunctionSpec(name="fn", family="t",
                                              variant=variant))
        return w_delta, w_full

    def test_delta_spec_serves_same_logits(self, tmp_path):
        from repro.serving import ColdStartOptions, InvocationRequest, Strategy

        w_delta, w_full = self._worker_pair(tmp_path)
        toks = np.arange(8, dtype=np.int32).reshape(1, 8) % 256
        req = InvocationRequest(
            function="fn", tokens=toks,
            options=ColdStartOptions(strategy=Strategy.SNAPFAAS,
                                     force_cold=True),
        )
        r_delta = w_delta.invoke(req)
        r_full = w_full.invoke(req)
        np.testing.assert_allclose(r_delta.output, r_full.output,
                                   rtol=1e-5, atol=1e-6)
        # the delta worker stored base + delta once; the dedup view knows
        s = w_delta.registry.dedup_stats()
        assert s["unique_bytes"] < s["referenced_bytes"]

    def test_worker_deregister(self, tmp_path):
        from repro.serving import InvocationRequest

        w_delta, _ = self._worker_pair(tmp_path)
        toks = np.arange(8, dtype=np.int32).reshape(1, 8) % 256
        w_delta.invoke(InvocationRequest(function="fn", tokens=toks))
        freed = w_delta.deregister_function("fn")
        assert freed > 0                     # its wq delta chunks died
        assert "fn" not in w_delta.specs
        assert "fn" not in w_delta.registry.functions
        with pytest.raises(KeyError):
            w_delta.invoke(InvocationRequest(function="fn", tokens=toks))


# ------------------------------------------------- at-rest pack corruption

def _flip_on_disk(store, digest):
    """Flip one byte of ``digest``'s stored payload in its pack file.
    ChunkStore maps packs with ``ACCESS_READ`` (shared), so the rot is
    visible through live mmaps — the on-disk bit-rot scenario."""
    loc = store.local.location(digest)
    path = os.path.join(store.local.root, "packs", f"{loc.pack}.pack")
    with open(path, "r+b") as f:
        f.seek(loc.offset)
        orig = f.read(1)
        f.seek(loc.offset)
        f.write(bytes([orig[0] ^ 0xFF]))


class TestPackCorruption:
    """Bit-rot a stored chunk and assert every strategy either repairs it
    or raises :class:`ChunkIntegrityError` — wrong bytes are never served."""

    def _registry(self, tmp_path, name):
        reg = ZygoteRegistry(
            str(tmp_path / name), chunk_bytes=CHUNK,
            tiers=TierSpec(ram_bytes=0, remote_bw=10e9, remote_lat=0.0),
        )
        reg.register_runtime("fam", _tree(0))
        return reg

    def _register_fn(self, reg, seed=42):
        rng = np.random.default_rng(seed)
        delta = {"head/w": rng.standard_normal((64, 64)).astype(np.float32)}
        reg.register_from_base("fn", "fam", {k: np.array(v)
                                             for k, v in delta.items()})
        _touch_all(reg, "fn", extra=delta)
        full_flat = dict(flatten_pytree(_tree(0)))
        full_flat.update(delta)
        return full_flat, delta

    def _diff_refs(self, reg):
        """The function's own (non-base, non-zero) diff chunks."""
        base_digests = set(manifest_digests(reg.bases["fam"]))
        rec = reg.functions["fn"]
        return [c for a in rec.diff.arrays.values() for c in a.chunks
                if c is not None and not c.zero
                and c.digest not in base_digests
                and c.digest in reg.store.local]

    @pytest.mark.parametrize(
        "strategy", ("regular", "reap", "seuss", "snapfaas-", "snapfaas")
    )
    def test_corrupt_diff_chunk_never_serves_wrong_bytes(
        self, tmp_path, strategy
    ):
        reg = self._registry(tmp_path, f"reg-{strategy}")
        full_flat, delta = self._register_fn(reg)
        refs = self._diff_refs(reg)
        assert refs, "expected at least one private diff chunk"
        _flip_on_disk(reg.store, refs[0].digest)

        kw = _loaders(full_flat, set(delta))
        extra = kw if strategy in ("seuss", "regular") else {}
        from repro.core import PLANNED_STRATEGIES
        if strategy in PLANNED_STRATEGIES:
            # the chunk exists nowhere else (no remote copy, not base
            # content): repair has no source, so the restore REFUSES —
            # typed, never wrong bytes
            with pytest.raises(ChunkIntegrityError) as exc:
                reg.cold_start("fn", strategy, **extra)
            assert exc.value.digest == refs[0].digest
            assert (refs[0].digest, "local") in reg.store.quarantined
        else:
            # seuss/regular boot from source artifacts, not the store —
            # the rot is invisible to them and the restore is correct
            inst = reg.cold_start("fn", strategy, **extra)
            for path, expected in full_flat.items():
                np.testing.assert_array_equal(inst.value(path), expected,
                                              err_msg=f"{strategy}/{path}")

    def test_corrupt_base_chunk_repaired_from_shared_base(self, tmp_path):
        reg = self._registry(tmp_path, "reg-base")
        store = reg.store
        digests = [d for d in manifest_digests(reg.bases["fam"])
                   if d in store.local]
        rec_refs = {c.digest: c for a in reg.bases["fam"].arrays.values()
                    for c in a.chunks if c is not None and not c.zero}
        digest = next(d for d in digests if d in rec_refs)
        ref = rec_refs[digest]
        want = store.get_chunk(ref)
        _flip_on_disk(store, digest)
        # verified read catches the rot; the registry's base pool is wired
        # in as a fallback source, so the chunk is re-synthesized from the
        # shared base — and the corrupt pack copy is quarantined
        assert store.get_chunk(ref) == want
        health = store.tier_stats()["health"]
        assert health["verify_failures"] >= 1
        assert health["repaired_chunks"] >= 1
        assert (digest, "local") in store.quarantined

    def test_corrupt_local_copy_repaired_from_remote(self, tmp_path):
        reg = self._registry(tmp_path, "reg-dual")
        self._register_fn(reg)
        store = reg.store
        refs = self._diff_refs(reg)
        ref = refs[0]
        want = store.get_chunk(ref)
        # make the chunk dual-resident (remote + local), then rot the
        # LOCAL copy only
        store.demote([ref])
        store.prefetch([ref])
        store.join_promotions()
        assert ref.digest in store.local
        _flip_on_disk(store, ref.digest)
        assert store.get_chunk(ref) == want     # healed from the remote tier
        health = store.tier_stats()["health"]
        assert health["repaired_chunks"] >= 1
        # full restore still byte-identical after the repair
        inst = reg.cold_start("fn", "snapfaas")
        rng = np.random.default_rng(42)
        np.testing.assert_array_equal(
            inst.value("head/w"),
            rng.standard_normal((64, 64)).astype(np.float32),
        )


# ------------------------------------------------------ hypothesis property

@st.composite
def _function_set(draw):
    n_fns = draw(st.integers(1, 3))
    fns = []
    for i in range(n_fns):
        # per base array: untouched / partially dirty / fully rewritten
        modes = tuple(
            draw(st.sampled_from(["clean", "partial", "rewrite"]))
            for _ in range(3)
        )
        new_array = draw(st.booleans())
        fns.append((modes, new_array))
    return fns


class TestCasVsFlatProperty:
    @settings(max_examples=8, deadline=None)
    @given(fns=_function_set(), seed=st.integers(0, 2 ** 16))
    def test_cas_restores_match_flat_and_store_less(
        self, tmp_path_factory, fns, seed
    ):
        """PROPERTY: for any random function set sharing a base,
        (1) every strategy's CAS restore is byte-identical to the flat
            (per-function store) restore, and
        (2) bytes_stored(CAS) <= bytes_stored(flat), with equality exactly
            when no two snapshots share a single chunk digest."""
        tmp = tmp_path_factory.mktemp("cas_prop")
        rng = np.random.default_rng(seed)
        base = _tree(seed % 7, rows=32)
        base_flat = flatten_pytree(base)

        reg = ZygoteRegistry(str(tmp / "cas"), chunk_bytes=512)
        reg.register_runtime("fam", base)

        flat_bytes = 0
        flat_base = ChunkStore(str(tmp / "flat-base"))
        take_snapshot(flat_base, "base", base, chunk_bytes=512)
        flat_bytes += flat_base.stored_bytes()

        fulls = {}
        for i, (modes, new_array) in enumerate(fns):
            name = f"fn{i}"
            full = {p: np.array(a) for p, a in base_flat.items()}
            for j, mode in enumerate(modes):
                p = f"l{j}/w"
                if mode == "partial":
                    full[p][0, :] = rng.standard_normal(
                        full[p].shape[1]).astype(np.float32)
                elif mode == "rewrite":
                    full[p] = rng.standard_normal(
                        full[p].shape).astype(np.float32)
            if new_array:
                full[f"extra{i}/w"] = rng.standard_normal(
                    (8, 8)).astype(np.float32)
            delta = {f"l{j}/w": full[f"l{j}/w"]
                     for j, mode in enumerate(modes) if mode != "clean"}
            delta.update({p: full[p] for p in full if p.startswith("extra")})
            fulls[name] = full

            reg.register_from_base(name, "fam", dict(delta))
            log = AccessLog()
            for p in full:
                log.touch(p)
            reg.generate_working_set(name, log)

            fstore = ChunkStore(str(tmp / f"flat-{name}"))
            take_snapshot(fstore, f"full-{name}", full, chunk_bytes=512)
            flat_bytes += fstore.stored_bytes()
            fstore.close()

        # (2) storage: CAS never stores more; equality iff nothing shared
        cas_bytes = reg.store.stored_bytes()
        assert cas_bytes <= flat_bytes
        owners = [set(manifest_digests(reg.bases["fam"]))]
        owners += [set(manifest_digests(reg.functions[f"fn{i}"].full))
                   for i in range(len(fns))]
        counts = {}
        for s in owners:
            for d in s:
                counts[d] = counts.get(d, 0) + 1
        anything_shared = any(c > 1 for c in counts.values())
        assert (cas_bytes < flat_bytes) == anything_shared

        # (1) restores: byte-identical to the source of truth (and hence
        # to what a flat per-function store would restore) on all 5
        for i in range(len(fns)):
            name = f"fn{i}"
            full_flat = fulls[name]
            delta_paths = {p for p in full_flat
                           if p.startswith("extra")
                           or not np.array_equal(full_flat[p], base_flat.get(
                               p, np.empty(0)))}
            kw = _loaders(full_flat, delta_paths)
            for strategy in ("regular", "reap", "seuss",
                             "snapfaas-", "snapfaas"):
                extra = kw if strategy in ("seuss", "regular") else {}
                inst = reg.cold_start(name, strategy, **extra)
                for path, expected in full_flat.items():
                    np.testing.assert_array_equal(
                        inst.value(path), expected,
                        err_msg=f"{name}/{strategy}/{path}",
                    )
