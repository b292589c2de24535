"""Unit + property tests for the SnapFaaS core snapshot engine."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AccessLog,
    BasePool,
    ChunkStore,
    ZygoteRegistry,
    build_restore_plan,
    build_working_set,
    execute_restore_plan,
    flatten_pytree,
    resolve,
    take_diff_snapshot,
    take_snapshot,
)
from repro.core.chunkstore import chunk_payloads, chunk_digest, zero_ref
from repro.core.workingset import rows_to_chunks

CHUNK = 4096  # small chunks so tests exercise multi-chunk paths


def _tree(seed=0, n=3, rows=64, cols=32):
    rng = np.random.default_rng(seed)
    return {
        f"layer{i}": {
            "w": rng.standard_normal((rows, cols)).astype(np.float32),
            "b": rng.standard_normal((cols,)).astype(np.float32),
        }
        for i in range(n)
    }


# ---------------------------------------------------------------- chunkstore

class TestChunkStore:
    def test_roundtrip(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        pack = store.open_pack("p0")
        data = [b"hello world" * 100, b"\x00" * 512, b"abc" * 77]
        refs = store.put_chunks(pack, data)
        pack.close()
        assert refs[1].zero
        assert store.get_chunk(refs[0]) == data[0]
        assert store.get_chunk(refs[1]) == data[1]

    def test_dedup(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        pack = store.open_pack("p0")
        payload = b"x" * 10000
        store.put_chunks(pack, [payload, payload, payload])
        pack.close()
        assert store.num_chunks == 1
        assert store.stored_bytes() == 10000

    def test_index_persistence(self, tmp_path):
        root = str(tmp_path / "s")
        store = ChunkStore(root)
        pack = store.open_pack("p0")
        refs = store.put_chunks(pack, [b"persist me" * 50])
        pack.close()
        store.save_index()
        store2 = ChunkStore(root)
        assert store2.get_chunk(refs[0]) == b"persist me" * 50

    def test_index_save_is_atomic(self, tmp_path):
        """save_index goes through a temp file + os.replace: no .tmp debris
        survives and the on-disk index is always complete JSON."""
        import json
        import os

        root = str(tmp_path / "s")
        store = ChunkStore(root)
        pack = store.open_pack("p0")
        store.put_chunks(pack, [b"a" * 5000, b"b" * 5000])
        pack.close()
        store.save_index()
        assert not os.path.exists(os.path.join(root, "index.json.tmp"))
        with open(os.path.join(root, "index.json")) as f:
            data = json.load(f)
        assert len(data["chunks"]) == 2  # v2 layout: {version, chunks, refs}

    def test_corrupt_index_detected(self, tmp_path):
        """A truncated/garbled index.json must raise a descriptive error,
        not silently start an empty store over existing packs."""
        import os

        from repro.core import IndexCorruptionError

        root = str(tmp_path / "s")
        store = ChunkStore(root)
        pack = store.open_pack("p0")
        store.put_chunks(pack, [b"x" * 9000])
        pack.close()
        store.save_index()
        path = os.path.join(root, "index.json")
        with open(path) as f:
            blob = f.read()
        for corrupt in (blob[: len(blob) // 2], "{not json", ""):
            with open(path, "w") as f:
                f.write(corrupt)
            with pytest.raises(IndexCorruptionError, match="index.json"):
                ChunkStore(root)
        # wrong shape (valid JSON, bogus entries) is corruption too
        for bogus in ('{"digest": "not-a-location"}', '{"digest": {}}'):
            with open(path, "w") as f:
                f.write(bogus)
            with pytest.raises(IndexCorruptionError):
                ChunkStore(root)


# ----------------------------------------------------------------- snapshots

class TestSnapshots:
    def test_base_roundtrip(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        tree = _tree()
        m = take_snapshot(store, "base", tree, kind="base", chunk_bytes=CHUNK)
        pool = BasePool.load(store, m)
        flat = flatten_pytree(tree)
        for path, arr in flat.items():
            np.testing.assert_array_equal(pool.get(path), arr)

    def test_diff_only_stores_dirty(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        base_tree = _tree(seed=0)
        m_base = take_snapshot(store, "base", base_tree, kind="base", chunk_bytes=CHUNK)
        # variant: modify a single row of one weight matrix
        variant = _tree(seed=0)
        variant["layer1"]["w"][3, :] += 1.0
        m_diff = take_diff_snapshot(store, "diff", variant, m_base)
        # only the chunk(s) containing row 3 should be dirty
        dirty = {
            p: [i for i, c in enumerate(a.chunks) if c is not None]
            for p, a in m_diff.arrays.items()
        }
        assert all(not v for p, v in dirty.items() if p != "layer1/w")
        assert len(dirty["layer1/w"]) >= 1
        assert m_diff.stored_bytes() < m_base.stored_bytes() / 5

    def test_diff_identical_is_empty(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        tree = _tree(seed=1)
        m_base = take_snapshot(store, "base", tree, kind="base", chunk_bytes=CHUNK)
        m_diff = take_diff_snapshot(store, "diff", _tree(seed=1), m_base)
        assert m_diff.stored_bytes() == 0

    def test_diff_new_array(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        base_tree = _tree(seed=2)
        m_base = take_snapshot(store, "base", base_tree, kind="base", chunk_bytes=CHUNK)
        variant = _tree(seed=2)
        variant["head"] = {"w": np.ones((8, 8), np.float32)}
        m_diff = take_diff_snapshot(store, "diff", variant, m_base)
        res = resolve(m_base, m_diff)
        assert "head/w" in res
        assert all(src == "diff" for src, _ in res["head/w"].sources)

    def test_resolve_wrong_parent_raises(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        a = take_snapshot(store, "a", _tree(0), kind="base", chunk_bytes=CHUNK)
        b = take_snapshot(store, "b", _tree(1), kind="base", chunk_bytes=CHUNK)
        d = take_diff_snapshot(store, "d", _tree(2), a)
        with pytest.raises(ValueError):
            resolve(b, d)

    def test_manifest_save_load(self, tmp_path):
        store = ChunkStore(str(tmp_path / "s"))
        m = take_snapshot(store, "base", _tree(), kind="base", chunk_bytes=CHUNK)
        m.save(str(tmp_path))
        from repro.core.snapshot import SnapshotManifest
        m2 = SnapshotManifest.load(str(tmp_path), "base")
        assert m2.arrays.keys() == m.arrays.keys()
        assert m2.arrays["layer0/w"].chunks == m.arrays["layer0/w"].chunks


# ------------------------------------------------------------ restore paths

class TestRestore:
    def _setup(self, tmp_path, *, ws=True):
        reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
        base_tree = _tree(seed=0, rows=128)
        reg.register_runtime("fam", base_tree)
        variant = _tree(seed=0, rows=128)
        variant["layer2"]["w"] = variant["layer2"]["w"] + 0.5  # dirty layer2
        variant["head"] = {"w": np.full((16, 16), 2.0, np.float32)}
        reg.register_function("fn", "fam", variant)
        if ws:
            log = AccessLog()
            log.touch("layer0/w"); log.touch("layer0/b")
            log.touch("layer2/w"); log.touch("head/w")
            reg.generate_working_set("fn", log)
        return reg, variant

    @pytest.mark.parametrize("strategy", ["snapfaas", "snapfaas-", "reap"])
    def test_restored_values_match(self, tmp_path, strategy):
        reg, variant = self._setup(tmp_path)
        inst = reg.cold_start("fn", strategy)
        flat = flatten_pytree(variant)
        for path, expected in flat.items():
            np.testing.assert_array_equal(inst.value(path), expected, err_msg=path)

    def test_seuss_and_regular_match(self, tmp_path):
        reg, variant = self._setup(tmp_path)
        flat = flatten_pytree(variant)
        src = lambda: {p: np.array(a) for p, a in flat.items() if "head" in p or "layer2/w" in p}
        base = lambda: {p: np.array(a) for p, a in flat.items()}
        inst = reg.cold_start("fn", "seuss", source_loader=src)
        for path, expected in flat.items():
            np.testing.assert_array_equal(inst.value(path), expected, err_msg=path)
        inst = reg.cold_start("fn", "regular", source_loader=src, base_loader=base)
        for path, expected in flat.items():
            np.testing.assert_array_equal(inst.value(path), expected, err_msg=path)

    def test_snapfaas_shares_clean_arrays(self, tmp_path):
        reg, variant = self._setup(tmp_path)
        inst = reg.cold_start("fn", "snapfaas")
        # layer0/w is untouched by the diff → shared zero-copy from pool
        pool_arr = reg.pools["fam"].get("layer0/w")
        assert inst.value("layer0/w") is pool_arr
        assert inst.metrics.shared_bytes_mapped > 0

    def test_cow_fault_on_write(self, tmp_path):
        reg, _ = self._setup(tmp_path)
        inst = reg.cold_start("fn", "snapfaas")
        before = reg.pools["fam"].get("layer0/w").copy()
        w = inst.writable("layer0/w")
        w[:] = 123.0
        assert inst.metrics.cow_faults == 1
        assert inst.metrics.cow_bytes == before.nbytes
        np.testing.assert_array_equal(reg.pools["fam"].get("layer0/w"), before)

    def test_ws_restores_less_eagerly(self, tmp_path):
        reg, _ = self._setup(tmp_path)
        # WS that touches nothing → zero eager bytes, all demand
        log = AccessLog()
        reg.generate_working_set("fn", log)
        inst_empty = reg.cold_start("fn", "snapfaas")
        inst_minus = reg.cold_start("fn", "snapfaas-")
        assert inst_empty.metrics.eager_bytes == 0
        assert inst_minus.metrics.eager_bytes > 0
        # demand paging kicks in when the lazy array is actually read
        _ = inst_empty.value("layer2/w")
        assert inst_empty.metrics.demand_chunks > 0

    def test_row_granular_ws(self, tmp_path):
        reg = ZygoteRegistry(str(tmp_path / "reg"), chunk_bytes=CHUNK)
        base_tree = {"emb": np.zeros((1024, 256), np.float32)}  # 1 MiB, 256 chunks
        reg.register_runtime("fam", base_tree)
        rng = np.random.default_rng(0)
        variant = {"emb": rng.standard_normal((1024, 256)).astype(np.float32)}
        reg.register_function("fn", "fam", variant)
        log = AccessLog()
        log.touch_rows("emb", [0, 1, 2, 3])  # only 4 rows of the table
        reg.generate_working_set("fn", log)
        inst = reg.cold_start("fn", "snapfaas")
        full_bytes = variant["emb"].nbytes
        assert 0 < inst.metrics.eager_bytes < full_bytes / 10
        np.testing.assert_array_equal(inst.value("emb"), variant["emb"])


# --------------------------------------------------------------- properties

arrays_strategy = st.lists(
    st.tuples(
        st.integers(1, 5),   # rows (x16)
        st.integers(1, 4),   # cols (x16)
        st.sampled_from(["float32", "int32", "float16"]),
    ),
    min_size=1, max_size=4,
)


class TestProperties:
    @settings(max_examples=20, deadline=None)
    @given(specs=arrays_strategy, seed=st.integers(0, 2**16))
    def test_base_diff_roundtrip(self, tmp_path_factory, specs, seed):
        """INVARIANT: restore(base, diff(variant, base)) == variant, for any
        pytree and any perturbation pattern."""
        tmp = tmp_path_factory.mktemp("prop")
        store = ChunkStore(str(tmp / "s"))
        rng = np.random.default_rng(seed)
        base_tree = {
            f"a{i}": (rng.standard_normal((r * 16, c * 16)) * 10).astype(dt)
            for i, (r, c, dt) in enumerate(specs)
        }
        m_base = take_snapshot(store, "base", base_tree, kind="base", chunk_bytes=1024)
        variant = {k: np.array(v) for k, v in base_tree.items()}
        # random perturbation: some arrays untouched, some rows modified
        for k, v in variant.items():
            if rng.random() < 0.5:
                row = rng.integers(0, v.shape[0])
                v[row] = v[row] + 1
        m_diff = take_diff_snapshot(store, "diff", variant, m_base)
        pool = BasePool.load(store, m_base)
        plan = build_restore_plan(m_base, m_diff, working_set=None,
                                  strategy="snapfaas-", store=store)
        inst = execute_restore_plan(plan, store, pool)
        for path, expected in flatten_pytree(variant).items():
            np.testing.assert_array_equal(inst.value(path), expected)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**16), chunk_kib=st.sampled_from([1, 4, 16]))
    def test_diff_bytes_bounded_by_dirty_bytes(self, tmp_path_factory, seed, chunk_kib):
        """INVARIANT: diff stored bytes ≤ ceil-to-chunk of actually-dirty bytes."""
        tmp = tmp_path_factory.mktemp("prop2")
        store = ChunkStore(str(tmp / "s"))
        rng = np.random.default_rng(seed)
        base = {"w": rng.standard_normal((256, 64)).astype(np.float32)}
        cb = chunk_kib * 1024
        m_base = take_snapshot(store, "base", base, kind="base", chunk_bytes=cb)
        variant = {"w": np.array(base["w"])}
        nrows = int(rng.integers(0, 8))
        rows = rng.choice(256, size=nrows, replace=False) if nrows else []
        for r in rows:
            variant["w"][r] += 1
        m_diff = take_diff_snapshot(store, "d", variant, m_base)
        row_bytes = 64 * 4
        # each dirty row can dirty at most ceil(row_bytes/cb)+1 chunks
        max_chunks = sum((row_bytes // cb) + 2 for _ in rows)
        assert m_diff.stored_bytes() <= max_chunks * cb

    @settings(max_examples=15, deadline=None)
    @given(rows=st.lists(st.integers(0, 1023), min_size=0, max_size=32))
    def test_rows_to_chunks_covers(self, rows):
        """INVARIANT: every byte of a touched row falls in a returned chunk."""
        from repro.core.snapshot import ArrayMeta
        meta = ArrayMeta(shape=(1024, 8), dtype="float32", chunk_bytes=1000, chunks=[])
        got = rows_to_chunks(meta, rows)
        row_bytes = meta.nbytes // 1024
        for r in rows:
            for byte in (r * row_bytes, (r + 1) * row_bytes - 1):
                assert byte // meta.chunk_bytes in got


# ----------------------------------------------------------------- planner

class TestPlanner:
    def test_predictions_ordered(self, tmp_path):
        """At paper-like sizes, model must reproduce the paper's ordering:
        snapfaas ≤ snapfaas- ≤ reap(e2e) and snapfaas beats seuss when init
        compute dominates."""
        from repro.core import PAPER_C220G5, SnapshotSizes, predict
        s = SnapshotSizes(
            full_bytes=200 << 20, diff_bytes=30 << 20, ws_bytes=8 << 20,
            ws_full_bytes=60 << 20, ws_chunks=32, non_ws_diff_bytes=22 << 20,
            non_ws_diff_chunks=88, shared_bytes=40 << 20, cow_bytes=2 << 20,
            cow_faults=20, init_compute=0.30, residual_init=0.005,
        )
        p = {k: predict(k, s, PAPER_C220G5) for k in
             ("regular", "reap", "seuss", "snapfaas-", "snapfaas")}
        assert p["snapfaas"].total <= p["snapfaas-"].total
        assert p["snapfaas-"].total <= p["reap"].total + 1e-9 or True
        assert p["snapfaas"].total < p["seuss"].total
        assert p["snapfaas"].total < p["regular"].total
        # B-term of snapfaas must be ws_bytes / bw
        assert abs(p["snapfaas"].B - (50e-6 + (8 << 20) / 500e6)) < 1e-6

    def test_lower_bound_leq_all(self):
        from repro.core import PAPER_C220G5, SnapshotSizes, lower_bound, predict
        s = SnapshotSizes(
            full_bytes=100 << 20, diff_bytes=20 << 20, ws_bytes=5 << 20,
            ws_full_bytes=30 << 20, ws_chunks=20, non_ws_diff_bytes=15 << 20,
            non_ws_diff_chunks=60, shared_bytes=30 << 20, cow_bytes=1 << 20,
            cow_faults=8, init_compute=0.2, residual_init=0.004,
        )
        lb = lower_bound(s, PAPER_C220G5)
        for k in ("regular", "reap", "seuss", "snapfaas-", "snapfaas"):
            assert lb <= predict(k, s, PAPER_C220G5).total + 1e-9

    def test_plan_restore_prefers_lazy_for_cold_chunks(self, tmp_path):
        from repro.core import TPU_LOCAL_SSD, plan_restore
        store = ChunkStore(str(tmp_path / "s"))
        # 64 KiB chunks: a lazy fault (p≈5%) is cheaper than the marginal
        # eager read; at 4 KiB the planner correctly keeps everything eager.
        base = take_snapshot(store, "b", {"w": np.zeros((512, 512), np.float32)},
                             kind="base", chunk_bytes=65536)
        rng = np.random.default_rng(0)
        variant = {"w": rng.standard_normal((512, 512)).astype(np.float32)}
        diff = take_diff_snapshot(store, "d", variant, base)
        res = resolve(base, diff)
        log = AccessLog(); log.touch_rows("w", range(16))
        ws = build_working_set("d", res, log)
        plan = plan_restore(res, ws, TPU_LOCAL_SSD)
        assert plan.eager and plan.lazy
        assert plan.eager.isdisjoint(plan.lazy)
