"""Restore engine: restored bytes equal the variant wherever its chunks
live, scatter-read correctness, coalescing properties, and the on-device
patch path."""

import gc

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AccessLog,
    ChunkStore,
    ZygoteRegistry,
    flatten_pytree,
    resolve,
)
from repro.core.chunkstore import COALESCE_GAP, coalesce_ranges, scan_chunks

CHUNK = 4096


def _tree(seed=0, n=3, rows=128, cols=32):
    rng = np.random.default_rng(seed)
    return {
        f"layer{i}": {
            "w": rng.standard_normal((rows, cols)).astype(np.float32),
            "b": rng.standard_normal((cols,)).astype(np.float32),
        }
        for i in range(n)
    }


def _registry(tmp_path, *, ws=True):
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
    base_tree = _tree(seed=0)
    reg.register_runtime("fam", base_tree)
    variant = _tree(seed=0)
    variant["layer2"]["w"] = variant["layer2"]["w"] + 0.5       # dirty array
    variant["layer1"]["w"][:8] = 0.0                            # zeroed rows
    variant["head"] = {"w": np.full((16, 16), 2.0, np.float32)}  # new array
    reg.register_function("fn", "fam", variant)
    if ws:
        log = AccessLog()
        for p in ("layer0/w", "layer0/b", "layer1/w", "layer2/w", "head/w"):
            log.touch(p)
        reg.generate_working_set("fn", log)
    return reg, variant


# ---------------------------------------------------------- engine equivalence

# the eager set each snapshot strategy reads, as a prefetch category
EAGER_CATEGORY = {"snapfaas": "ws", "snapfaas-": "diff", "reap": "ws_full"}


def _expected_eager(reg, strategy):
    """(bytes, chunks) of the non-zero dirty working-set chunks, summed
    from the manifests: what a restore of ``strategy`` reads eagerly."""
    rec = reg.functions["fn"]
    if strategy == "reap":   # every chunk of the full snapshot reads as dirty
        resolved, ws = resolve(None, rec.full), rec.ws_full
    else:
        resolved = resolve(reg.bases["fam"], rec.diff)
        ws = rec.ws if strategy == "snapfaas" else None
    refs = [
        ra.sources[i][1] for path, ra in resolved.items()
        for i in ra.dirty_indices()
        if not ra.sources[i][1].zero and (ws is None or (path, i) in ws)
    ]
    return sum(r.size for r in refs), len(refs)


class TestEngineEquivalence:
    @pytest.mark.parametrize("tier", ["ram", "local", "remote"])
    @pytest.mark.parametrize("strategy", ["snapfaas", "snapfaas-", "reap"])
    def test_planned_matches_source_variant(self, tmp_path, strategy, tier):
        """Restored bytes equal the variant whichever tier holds the eager
        set: the RAM cache (prefetched), the local packs only, or the
        remote tier (the function's own chunks demoted)."""
        reg, variant = _registry(tmp_path)
        reg.store.drop_page_cache(clear_ram=True)
        if tier == "ram":
            reg.prefetch_working_set("fn", EAGER_CATEGORY[strategy])
        elif tier == "remote":
            assert reg.demote_function("fn") > 0
        inst = reg.cold_start("fn", strategy)
        for path, expected in flatten_pytree(variant).items():
            np.testing.assert_array_equal(inst.value(path), expected, err_msg=path)
        m = inst.metrics
        assert m.tier_bytes.get(tier, 0) > 0, m.tier_bytes
        assert sum(m.tier_bytes.values()) == m.eager_bytes
        if tier != "remote":   # reap's base chunks stay local when demoted
            assert set(m.tier_bytes) == {tier}, m.tier_bytes

    def test_seuss_and_regular_match(self, tmp_path):
        """The loader strategies restore the same values (they bypass the
        plan engine; included so all five strategies are pinned here)."""
        reg, variant = _registry(tmp_path)
        flat = flatten_pytree(variant)
        src = lambda: {p: np.array(a) for p, a in flat.items()
                       if "head" in p or "layer2/w" in p or "layer1/w" in p}
        base = lambda: {p: np.array(a) for p, a in flat.items()}
        for strategy, kw in (
            ("seuss", dict(source_loader=src)),
            ("regular", dict(source_loader=src, base_loader=base)),
        ):
            inst = reg.cold_start("fn", strategy, **kw)
            for path, expected in flat.items():
                np.testing.assert_array_equal(
                    inst.value(path), expected, err_msg=f"{strategy}/{path}"
                )

    def test_plan_is_cached_and_invalidated(self, tmp_path):
        reg, _ = _registry(tmp_path)
        reg.cold_start("fn", "snapfaas")
        rec = reg.functions["fn"]
        plan = rec.plans["snapfaas"]
        reg.cold_start("fn", "snapfaas")
        assert rec.plans["snapfaas"] is plan  # cached, not rebuilt
        reg.generate_working_set("fn", AccessLog())  # WS change → stale
        assert not rec.plans

    def test_eager_accounting_matches_legacy(self, tmp_path):
        reg, _ = _registry(tmp_path)
        for strategy in ("snapfaas", "snapfaas-", "reap"):
            m = reg.cold_start("fn", strategy).metrics
            want = _expected_eager(reg, strategy)
            assert want[1] > 0, strategy
            assert (m.eager_bytes, m.eager_chunks) == want, strategy

    def test_demand_paging_still_works(self, tmp_path):
        """With an empty WS nothing is eager; first read demand-faults."""
        reg, variant = _registry(tmp_path)
        reg.generate_working_set("fn", AccessLog())
        inst = reg.cold_start("fn", "snapfaas")
        assert inst.metrics.eager_bytes == 0
        np.testing.assert_array_equal(
            inst.value("layer2/w"), variant["layer2"]["w"]
        )
        assert inst.metrics.demand_chunks > 0


# ------------------------------------------------------------- scatter reads

class TestReadBatchInto:
    def _store(self, tmp_path, n=40, size=5000, seed=0):
        store = ChunkStore(str(tmp_path / "s"))
        rng = np.random.default_rng(seed)
        payloads = [rng.integers(0, 255, size, dtype=np.uint8).tobytes()
                    for _ in range(n)]
        payloads[5] = b"\x00" * size
        pack = store.open_pack("p0")
        refs = store.put_chunks(pack, payloads)
        pack.close()
        return store, refs, payloads

    def test_scatter_into_views(self, tmp_path):
        store, refs, payloads = self._store(tmp_path)
        big = np.zeros(sum(r.size for r in refs), dtype=np.uint8)
        mv = memoryview(big)
        dests, off = [], 0
        for r in refs:
            dests.append((r, mv[off : off + r.size]))
            off += r.size
        store.read_batch_into(dests)
        assert bytes(big.tobytes()) == b"".join(
            b"\x00" * r.size if r.zero else p for r, p in zip(refs, payloads)
        )

    def test_duplicate_refs_read_once_replicated(self, tmp_path):
        store, refs, payloads = self._store(tmp_path)
        r = refs[0]
        bufs = [bytearray(r.size) for _ in range(4)]
        store.read_batch_into([(r, memoryview(b)) for b in bufs])
        assert all(bytes(b) == payloads[0] for b in bufs)

    def test_wrong_dest_size_raises(self, tmp_path):
        store, refs, _ = self._store(tmp_path)
        with pytest.raises(ValueError):
            store.read_batch_into([(refs[0], memoryview(bytearray(3)))])

    def test_serial_equals_parallel(self, tmp_path):
        store, refs, payloads = self._store(tmp_path, n=64)
        out = {}
        for parallel in (False, True):
            bufs = [bytearray(r.size) for r in refs]
            store.read_batch_into(
                [(r, memoryview(b)) for r, b in zip(refs, bufs)],
                parallel=parallel,
            )
            out[parallel] = [bytes(b) for b in bufs]
        assert out[False] == out[True]

    def test_scan_chunks_matches_per_chunk(self, tmp_path):
        rng = np.random.default_rng(3)
        blob = rng.integers(0, 255, 50000, dtype=np.uint8)
        blob[10000:20000] = 0
        buf = memoryview(blob.tobytes())
        from repro.core.chunkstore import chunk_digest, chunk_payloads, is_zero
        refs = scan_chunks(buf, 10000)
        for ref, p in zip(refs, chunk_payloads(buf, 10000)):
            assert ref.zero == is_zero(p)
            if not ref.zero:
                assert ref.digest == chunk_digest(p)
            assert ref.size == len(p)


# --------------------------------------------------------------- properties

ranges_strategy = st.lists(
    st.tuples(st.integers(0, 1 << 20), st.integers(1, 1 << 16)),
    min_size=0, max_size=64,
)


class TestCoalesceProperties:
    @settings(max_examples=50, deadline=None)
    @given(ranges=ranges_strategy, gap=st.sampled_from([0, 1, 4096, COALESCE_GAP]))
    def test_runs_cover_partition_and_order(self, ranges, gap):
        """INVARIANTS of the scatter-read planner:
        * every input range is a member of exactly one run;
        * each run covers all its members;
        * runs are sorted, non-overlapping, and separated by > gap;
        * within a run, consecutive members (in offset order) are ≤ gap apart.
        """
        runs = coalesce_ranges(ranges, gap=gap)
        seen = []
        prev_end = None
        for start, end, members in runs:
            assert members, "empty run"
            assert start < end
            if prev_end is not None:
                assert start > prev_end + gap  # else they would have merged
            prev_end = end
            last_end = None
            for i in members:
                off, size = ranges[i]
                assert start <= off and off + size <= end
                if last_end is not None:
                    assert off <= last_end + gap
                last_end = max(last_end or 0, off + size)
            assert min(ranges[i][0] for i in members) == start
            assert max(ranges[i][0] + ranges[i][1] for i in members) == end
            seen.extend(members)
        assert sorted(seen) == list(range(len(ranges)))

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2 ** 16), nzero=st.integers(0, 6))
    def test_roundtrip_random_store(self, tmp_path_factory, seed, nzero):
        """INVARIANT: scatter-read returns exactly what was stored, for any
        mix of zero/non-zero/duplicate chunks."""
        tmp = tmp_path_factory.mktemp("rb")
        store = ChunkStore(str(tmp / "s"))
        rng = np.random.default_rng(seed)
        payloads = []
        for i in range(12):
            if i < nzero:
                payloads.append(b"\x00" * int(rng.integers(1, 9000)))
            else:
                payloads.append(
                    rng.integers(0, 255, int(rng.integers(1, 9000)),
                                 dtype=np.uint8).tobytes()
                )
        pack = store.open_pack("p")
        refs = store.put_chunks(pack, payloads)
        pack.close()
        order = rng.permutation(len(refs))
        bufs = {int(i): bytearray(refs[i].size) for i in order}
        store.read_batch_into([(refs[i], memoryview(bufs[i])) for i in bufs])
        for i, b in bufs.items():
            expect = b"\x00" * refs[i].size if refs[i].zero else payloads[i]
            assert bytes(b) == expect


# ------------------------------------------------------------ recycled rows

def _patchable_registry(tmp_path, strategy):
    """Every dirty array patchable: one whose last chunk is partial, one
    with an all-zero diff chunk (the plan's zero row), one untouched."""
    reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
    rng = np.random.default_rng(3)
    base = {
        "tail": {"w": rng.standard_normal((100, 30)).astype(np.float32)},
        "zero": {"w": rng.standard_normal((64, 32)).astype(np.float32)},
        "same": {"w": rng.standard_normal((64, 32)).astype(np.float32)},
    }
    reg.register_runtime("fam", base)
    variant = {k: {"w": v["w"].copy()} for k, v in base.items()}
    variant["tail"]["w"] += 0.5              # 12,000 B: chunks 4096, 4096, 3808
    variant["zero"]["w"][:32] = 0.0          # chunk 0 all zero
    variant["zero"]["w"][32:] += 1.0
    reg.register_function("fn", "fam", variant)
    if strategy == "snapfaas":
        log = AccessLog()
        for p in ("tail/w", "zero/w"):
            log.touch(p)
        reg.generate_working_set("fn", log)
    return reg, flatten_pytree(variant)


def _rows(inst):
    return {p: ma.patch.rows for p, ma in inst.arrays.items() if ma.patch is not None}


class TestRecycledRows:
    @pytest.mark.parametrize("holder", [
        "none", "instance", "reshape", "dtype", "memoryview", "jnp"])
    @pytest.mark.parametrize("strategy", ["snapfaas", "snapfaas-"])
    def test_restore_into_fresh_or_recycled_rows(self, tmp_path, strategy, holder):
        """A dropped instance's rows come back to the next restore only when
        nothing views them; either way the restore is byte-equal to one into
        fresh buffers, zero row and partial tail included, the counter says
        which it was, and free blocks stay under the arena's cap."""
        import jax.numpy as jnp
        from repro.core import execute_restore_plan

        reg, flat = _patchable_registry(tmp_path, strategy)
        arena = reg.arena
        first = reg.cold_start("fn", strategy)
        plan = reg.functions["fn"].plans[strategy]
        assert first.metrics.recycled_bytes == 0
        assert set(_rows(first)) == {"tail/w", "zero/w"}
        assert plan.eager_bytes == sum(
            pa.patch_eager_bytes for pa in plan.arrays if pa.patch_sel is not None)
        assert plan.rows_bytes == arena.stats()["cap"]
        make = {
            "none": lambda r: None, "instance": lambda r: first,
            "reshape": lambda r: r.reshape(-1, CHUNK),
            "dtype": lambda r: r.view(np.float32),
            "memoryview": memoryview, "jnp": jnp.asarray,
        }[holder]
        views, viewed = [], set()
        for path, rows in _rows(first).items():
            rows[:] = 0xAB                   # stale bytes a recycled block keeps
            views.append(make(rows))
            # a jnp array views the block only where the CPU backend aliased it
            if holder != "none" and (holder != "jnp" or
                                     views[-1].unsafe_buffer_pointer() == rows.ctypes.data):
                viewed.add(path)
        del rows
        if holder != "instance":
            del first
        gc.collect()  # where JAX copied a source, it lets go at a collector pass

        second = reg.cold_start("fn", strategy)
        assert second.metrics.recycled_bytes == sum(
            pa.patch_eager_bytes for pa in plan.arrays
            if pa.patch_sel is not None and pa.path not in viewed)
        assert arena.stats()["free_bytes"] <= arena.stats()["cap"]
        # whatever still views a block reads the bytes it had
        held = (list(_rows(first).values()) if holder == "instance"
                else [v for v in views if v is not None])
        for v in held:
            assert np.all(np.asarray(v).reshape(-1).view(np.uint8) == 0xAB)
        del held
        fresh = execute_restore_plan(plan, reg.store, reg.pools["fam"])
        for path, rows in _rows(second).items():
            want = fresh.arrays[path].patch
            assert bytes(rows) == bytes(want.rows), path
            np.testing.assert_array_equal(second.arrays[path].patch.sel, want.sel)
        for path, expected in flat.items():
            np.testing.assert_array_equal(second.value(path), expected, err_msg=path)

        # free blocks past one restore's worth are trimmed, oldest first
        third = reg.cold_start("fn", strategy)
        del second, third, views, fresh
        if holder == "instance":
            del first
        stats = arena.stats()
        assert 0 < stats["free_bytes"] <= stats["cap"]
        assert stats["trims"] > 0


    def test_worker_recycles_rows_after_pool_drop(self, tmp_path):
        """Through the serving path: nothing keeps a dropped instance alive
        (no reference cycle waits for the collector), so the next cold
        start of the function reads into its rows."""
        import weakref

        import jax
        from repro.models import build_model
        from repro.models.config import ModelConfig
        from repro.serving import ColdStartOptions, InvocationRequest, Strategy
        from repro.serving.worker import FunctionSpec, Worker

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        worker = Worker(str(tmp_path / "w"), chunk_bytes=4096)
        base_params = model.init(0)
        worker.register_runtime("t", model, base_params)
        variant = {k: np.array(v) + (0.01 if k.endswith("wq") else 0.0)
                   for k, v in flatten_pytree(jax.tree.map(np.asarray, base_params)).items()}
        worker.register_function(FunctionSpec(name="fn", family="t", variant=variant))
        req = InvocationRequest(
            function="fn", tokens=np.zeros((1, 8), np.int32),
            options=ColdStartOptions(strategy=Strategy.SNAPFAAS, force_cold=True))

        first = worker.invoke(req)
        assert first.metrics.eager_bytes > 0 and first.metrics.recycled_bytes == 0
        gc.disable()
        try:
            inst = weakref.ref(worker.pool.get("fn"))
            worker.pool.drop("fn")
            assert inst() is None
        finally:
            gc.enable()
        gc.collect()  # where JAX copied a transfer source, it lets go at a collector pass
        second = worker.invoke(req)
        assert second.metrics.recycled_bytes == second.metrics.eager_bytes > 0
        np.testing.assert_array_equal(second.output, first.output)


# ------------------------------------------------------------- device patch

class TestDevicePatch:
    def test_patch_descriptor_matches_host_assembly(self, tmp_path):
        """Applying (sel, rows) over the pool content must reproduce the
        host-assembled array — validates the layout fed to the Pallas
        snapshot_patch kernel."""
        reg, variant = _registry(tmp_path)
        inst = reg.cold_start("fn", "snapfaas")
        ma = inst.arrays["layer2/w"]
        assert ma.patch is not None
        meta = ma.meta
        pool_arr = reg.pools["fam"].get("layer2/w")
        flat = np.array(pool_arr).reshape(-1).view(np.uint8).copy()
        rows = ma.patch.rows_2d()
        cb = meta.chunk_bytes
        for idx, sel_row in enumerate(ma.patch.sel):
            if sel_row < 0:
                continue
            lo = idx * cb
            size = min(cb, meta.nbytes - lo)
            flat[lo : lo + size] = rows[sel_row, :size]
        patched = flat.view(np.dtype(meta.dtype)).reshape(meta.shape)
        np.testing.assert_array_equal(patched, variant["layer2"]["w"])
        # and the host lazy path agrees
        np.testing.assert_array_equal(inst.value("layer2/w"), patched)

    def test_patch_apply_op_on_descriptor(self, tmp_path):
        """End-to-end: the jitted patch op over the plan's descriptor equals
        the variant array (this is exactly what the worker runs on-device)."""
        import jax.numpy as jnp
        from repro.kernels.snapshot_patch import patch_apply_op

        reg, variant = _registry(tmp_path)
        inst = reg.cold_start("fn", "snapfaas")
        ma = inst.arrays["layer2/w"]
        meta = ma.meta
        itemsize = np.dtype(meta.dtype).itemsize
        c = meta.chunk_bytes // itemsize
        n = meta.num_chunks()
        total = meta.nbytes // itemsize
        base = np.array(reg.pools["fam"].get("layer2/w")).reshape(-1)
        base = np.pad(base, (0, n * c - total))
        diff2d = ma.patch.rows_2d().view(np.dtype(meta.dtype))
        out = patch_apply_op(
            jnp.asarray(base.reshape(n, c)), jnp.asarray(diff2d),
            jnp.asarray(ma.patch.sel), mode="replace",
            interpret=True, use_kernel=False,
        )
        got = np.asarray(out).reshape(-1)[:total].reshape(meta.shape)
        np.testing.assert_array_equal(got, variant["layer2"]["w"])

    def test_worker_serves_patched_params(self, tmp_path):
        """Worker request path picks the device-patch branch and produces
        the logits of a never-snapshotted instance: the family's jitted
        forward on the variant tree."""
        jax = pytest.importorskip("jax")
        from repro.core import unflatten_paths
        from repro.models import Batch, build_model
        from repro.models.config import ModelConfig
        from repro.serving.trace import request_tokens
        from repro.serving.worker import FunctionSpec, Worker

        cfg = ModelConfig(
            name="t", family="dense", num_layers=2, d_model=64, num_heads=2,
            num_kv_heads=2, d_ff=128, vocab_size=256, tie_embeddings=True,
            dtype="float32",
        )
        model = build_model(cfg)
        worker = Worker(str(tmp_path / "w"), chunk_bytes=4096)
        base_params = model.init(0)
        worker.register_runtime("t", model, base_params)
        flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
        variant = {k: np.array(v) for k, v in flat.items()}
        for k in variant:
            if k.endswith("wq"):
                variant[k] = variant[k] + 0.01
        spec = FunctionSpec(name="fn", family="t", variant=variant)
        worker.register_function(spec)
        from repro.serving import ColdStartOptions, InvocationRequest, Strategy

        toks = request_tokens(spec, np.random.default_rng(0), cfg.vocab_size,
                              seq=8)

        res = worker.invoke(InvocationRequest(
            function="fn", tokens=toks,
            options=ColdStartOptions(strategy=Strategy.SNAPFAAS, force_cold=True),
        ))
        inst = worker.pool.get("fn")
        assert any(a._dev is not None for a in inst.arrays.values()), \
            "device patch path did not fire"
        fwd = jax.jit(lambda p, t: model.logits(p, Batch(tokens=t)))
        want = np.asarray(fwd(unflatten_paths(variant), toks))[:, -1, :8]
        np.testing.assert_allclose(res.output, want, rtol=1e-5, atol=1e-6)
