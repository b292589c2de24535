"""Shared benchmark substrate: the function suite + measurement helpers.

The suite mirrors the paper's Table 1: ten functions over a runtime family,
in three dependency classes — *adapter* (tiny diff: alexa-door/-reminder,
lorem, matmul), *head* (medium diff: thumbnail, img-resize, tpcc) and
*finetune* (large diff: sentiment-analysis, ocr, audio-fingerprint) — with
short and long execution variants (the paper's lorem vs ocr split).

The bench model is mid-size (≈60 MB of f32 state) so restore I/O is
measurable against execution; page cache is dropped between cold starts so
eager/demand reads hit the storage medium.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.snapshot import flatten_pytree
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.serving.api import ColdStartOptions, InvocationRequest, Strategy
from repro.serving.trace import request_tokens
from repro.serving.worker import FunctionSpec, Worker

BENCH_CFG = ModelConfig(
    name="faas-bench",
    family="dense",
    num_layers=4,
    d_model=384,
    num_heads=6,
    num_kv_heads=6,
    d_ff=1024,
    vocab_size=16384,
    tie_embeddings=True,
    dtype="float32",
)

# (name, class, exec_seq) — Table 1 analogue
SUITE = [
    ("lorem", "adapter", 16),
    ("matmul", "adapter", 16),
    ("alexa-door", "adapter", 32),
    ("alexa-reminder", "adapter", 32),
    ("thumbnail", "head", 32),
    ("img-resize", "head", 32),
    ("tpcc", "head", 128),
    ("sentiment-analysis", "finetune", 32),
    ("audio-fingerprint", "finetune", 64),
    ("ocr", "finetune", 256),
]

STRATEGIES = ["regular", "reap", "seuss", "snapfaas-", "snapfaas"]


def _suite_specs(root: str, base_flat, *, n_functions: Optional[int] = None,
                 seed: int = 0) -> List[FunctionSpec]:
    """Paper-style variant specs over ``base_flat`` (not yet registered)."""
    rng = np.random.default_rng(seed + 1)
    specs = []
    items = SUITE[: n_functions or len(SUITE)]
    src_dir = os.path.join(root, "sources")
    os.makedirs(src_dir, exist_ok=True)
    for i, (name, klass, exec_seq) in enumerate(items):
        variant = {k: np.array(v) for k, v in base_flat.items()}
        touched_rows: Dict[str, List[int]] = {}
        if klass == "adapter":
            rows = list(range(64 * i, 64 * i + 64))
            variant["embed/table"][rows] += 0.02 * rng.standard_normal(
                (64, variant["embed/table"].shape[1])
            ).astype(np.float32)
            touched_rows["embed/table"] = rows
        elif klass == "head":
            variant["embed/table"] = variant["embed/table"] * 1.01
        else:  # finetune: every block weight
            for k in variant:
                if "blocks/" in k and k.endswith(("wq", "wk", "wv", "wo",
                                                  "w_in", "w_gate", "w_out")):
                    variant[k] = variant[k] + 0.005
        src = os.path.join(src_dir, f"{name}.npz")
        np.savez(src, **{k: v for k, v in variant.items()
                         if not np.array_equal(v, base_flat[k])})
        spec = FunctionSpec(name=name, family=BENCH_CFG.name, variant=variant,
                            touched=None, touched_rows=touched_rows,
                            source_path=src)
        spec.exec_seq = exec_seq  # type: ignore[attr-defined]
        spec.klass = klass        # type: ignore[attr-defined]
        specs.append(spec)
    return specs


def build_suite(root: str, *, n_functions: Optional[int] = None, seed: int = 0,
                tiers=None, prefetch_on_register: bool = True):
    """Worker + paper-style function suite over the bench family.

    ``tiers`` (a :class:`repro.core.tiers.TierSpec`) configures the worker's
    storage hierarchy — the tier benches use it to add a throttled remote."""
    model = build_model(BENCH_CFG)
    worker = Worker(os.path.join(root, "worker"), chunk_bytes=256 * 1024,
                    tiers=tiers, prefetch_on_register=prefetch_on_register)
    base_params = model.init(seed)
    worker.register_runtime(BENCH_CFG.name, model, base_params)
    base_flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
    specs = _suite_specs(root, base_flat, n_functions=n_functions, seed=seed)
    for spec in specs:
        worker.register_function(spec)
    return worker, specs


def build_cluster_suite(root: str, *, n_functions: Optional[int] = None,
                        seed: int = 0, n_workers: int = 2,
                        policy_factory=None, tiers=None,
                        pool_budget_bytes: int = 1 << 30,
                        max_concurrency: Optional[int] = None,
                        **cluster_kw):
    """Cluster + the same paper-style suite, sharded across ``n_workers``
    (the trace-serving bench substrate: runtime broadcast to every worker,
    functions registered on their home shards).  Extra keywords (e.g.
    ``placement``, ``steal``, ``admission``) pass through to
    :class:`~repro.serving.cluster.Cluster`."""
    from repro.serving.cluster import Cluster

    model = build_model(BENCH_CFG)
    cluster = Cluster(os.path.join(root, "cluster"), n_workers=n_workers,
                      chunk_bytes=256 * 1024, policy_factory=policy_factory,
                      tiers=tiers, pool_budget_bytes=pool_budget_bytes,
                      max_concurrency=max_concurrency, **cluster_kw)
    base_params = model.init(seed)
    cluster.register_runtime(BENCH_CFG.name, model, base_params)
    base_flat = flatten_pytree(jax.tree.map(np.asarray, base_params))
    specs = _suite_specs(root, base_flat, n_functions=n_functions, seed=seed)
    for spec in specs:
        cluster.register_function(spec)
    return cluster, specs


def build_delta_suite(root: str, *, n_functions: int = 4, seed: int = 0,
                      tiers=None):
    """Worker + N functions registered from ONE shared base via
    ``FunctionSpec.delta`` (content-addressed shared-base registration).

    Each function's delta perturbs a distinct 64-row band of the embedding
    table (adapter-style), so the functions share every other byte of the
    base model.  Returns ``(worker, specs, base_flat, register_times_s)``;
    registration prefetch is off so warm-tier effects are controlled by
    the caller."""
    model = build_model(BENCH_CFG)
    worker = Worker(os.path.join(root, "worker"), chunk_bytes=256 * 1024,
                    tiers=tiers, prefetch_on_register=False)
    base_params = model.init(seed)
    worker.register_runtime(BENCH_CFG.name, model, base_params)
    base_flat = flatten_pytree(jax.tree.map(np.asarray, base_params))

    rng = np.random.default_rng(seed + 1)
    src_dir = os.path.join(root, "sources")
    os.makedirs(src_dir, exist_ok=True)
    specs, reg_times = [], []
    for i in range(n_functions):
        rows = np.arange(64 * i, 64 * (i + 1))
        table = np.array(base_flat["embed/table"])
        table[rows] += 0.02 * rng.standard_normal(
            (len(rows), table.shape[1])
        ).astype(np.float32)
        delta = {"embed/table": table}
        src = os.path.join(src_dir, f"dedup-fn{i}.npz")
        np.savez(src, **delta)
        spec = FunctionSpec(name=f"dedup-fn{i}", family=BENCH_CFG.name,
                            delta=delta, source_path=src)
        spec.exec_seq = 16  # type: ignore[attr-defined]
        t0 = time.perf_counter()
        worker.register_function(spec)
        reg_times.append(time.perf_counter() - t0)
        specs.append(spec)
    return worker, specs, base_flat, reg_times


def drop_file_cache(paths) -> None:
    for path in paths:
        if not os.path.exists(path):
            continue
        fd = os.open(path, os.O_RDONLY)
        try:
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        finally:
            os.close(fd)


def cold_request(worker: Worker, spec, strategy: str, *, drop_cache: bool = True,
                 seed: int = 0, clear_ram: bool = True,
                 promote: bool | None = None):
    """One measured cold request (page cache dropped first — packs AND the
    npz source artifacts, so every strategy's reads hit the medium).

    ``clear_ram=False`` keeps the RAM chunk-cache tier warm across the
    drop (the warm-tier benches); ``promote`` is the tier hint forwarded
    to the restore (False keeps the *eager set* remote-resident across
    rounds — exec-time demand faults still follow the store default)."""
    if drop_cache:
        worker.registry.store.drop_page_cache(clear_ram=clear_ram)
        drop_file_cache(worker.source_files(spec.name))
    toks = request_tokens(spec, np.random.default_rng(seed),
                          BENCH_CFG.vocab_size, batch=1,
                          seq=getattr(spec, "exec_seq", 32))
    return worker.invoke(InvocationRequest(
        function=spec.name, tokens=toks,
        options=ColdStartOptions(strategy=Strategy.coerce(strategy),
                                 force_cold=True, promote=promote),
    ))


def rounds(worker: Worker, spec, strategy: str, n: int = 5, warmup: int = 1):
    """n measured cold rounds (after jit warmup via a warm request)."""
    out = []
    for r in range(warmup):
        cold_request(worker, spec, strategy, drop_cache=False, seed=r)
    for r in range(n):
        out.append(cold_request(worker, spec, strategy, seed=100 + r))
    return out


def csv_row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"


def update_bench_json(path: str, section: str, payload) -> None:
    """Merge one bench's machine-readable results into a shared JSON file
    (e.g. BENCH_coldstart.json) so future PRs have a perf trajectory to
    regress against."""
    import json

    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    data[section] = payload
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
