"""A head tied to the embedding reads every row of the table.

Row-granular materialization of ``embed/table`` (only the chunks covering
the request's rows fault in; the rest stay pending) is sound only where
the table is read by the gather alone.  A tied head reads all of
it, so every restore path that leaves chunks pending must still serve the
function's whole table: the logits equal ``Model.logits`` on the
function's own, never-snapshotted weights, whatever strategy restored it.

Widening the rows to those of the chunks the function changed is not
enough: a chunk the function shares with the base is pending too, and
holds no base content until it faults in.  ``adapter-far`` changes only
rows its requests gather, in a chunk away from the rows of the 8 logits
an answer returns, and fails exactly so when the table is row-granular.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.snapshot import flatten_pytree, unflatten_paths
from repro.models import Batch, build_model
from repro.serving import (
    Cluster,
    ColdStartOptions,
    FunctionSpec,
    InvocationRequest,
)

#: rows each adapter changes and gathers: in the chunk of the 8 logits an
#: answer returns (4,096 rows of 512 B, 512 rows a 256 KiB chunk), and in
#: another chunk
ADAPTER_ROWS = {"adapter": list(range(40, 56)), "adapter-far": list(range(1000, 1016))}
#: rows a head request's tokens come from: the upper half of the table,
#: far from the chunk that holds the rows of the 8 logits an answer returns
PROMPT_ROWS = (2048, 4096)

#: (strategy, demand_paged, record first): REAP replays a recording made
#: by a record-mode invocation, which is how it learns its working set
PATHS = [
    ("reap", False, True),
    ("regular", False, False),
    ("snapfaas", True, True),
    ("reap", True, True),
]


@pytest.fixture(scope="module")
def tied(tmp_path_factory):
    # 4,096 rows x 128 x float32: 2 MiB, many snapshot chunks
    cfg = dataclasses.replace(reduced(get_config("mamba2-780m")), vocab_size=4096)
    assert cfg.tie_embeddings
    model = build_model(cfg)
    base = flatten_pytree(jax.tree.map(np.asarray, model.init(0)))
    rng = np.random.default_rng(1)
    table = base["embed/table"]
    head = table + rng.standard_normal(table.shape).astype(table.dtype) * 0.004
    w_z = "blocks/pos0/w_z"
    deltas = {"head": {"embed/table": head}}
    for fn, rows in ADAPTER_ROWS.items():
        adapter = table.copy()
        adapter[rows] += rng.standard_normal(
            (len(rows), table.shape[1])).astype(table.dtype) * 0.004
        deltas[fn] = {"embed/table": adapter, w_z: base[w_z] + np.float32(0.01)}
    cluster = Cluster(str(tmp_path_factory.mktemp("tied")), n_workers=1)
    cluster.register_runtime(cfg.name, model, model.init(0))
    for fn, delta in deltas.items():
        cluster.register_function(FunctionSpec(
            name=fn, family=cfg.name, delta=delta,
            touched_rows={"embed/table": ADAPTER_ROWS[fn]} if fn in ADAPTER_ROWS else {}))
    fwd = jax.jit(lambda p, t: model.logits(p, Batch(tokens=t)))
    yield cluster, model, base, deltas, fwd
    cluster.shutdown()


def _tokens(fn, seed):
    rng = np.random.default_rng(seed)
    if fn in ADAPTER_ROWS:
        return rng.choice(np.asarray(ADAPTER_ROWS[fn]), size=(1, 16)).astype(np.int32)
    return rng.integers(*PROMPT_ROWS, size=(1, 16), dtype=np.int32)


@pytest.mark.parametrize("fn", ["head", "adapter", "adapter-far"])
@pytest.mark.parametrize("strategy,demand,record", PATHS,
                         ids=[f"{s}{'-demand' if d else ''}" for s, d, _ in PATHS])
def test_tied_table_served_whole(tied, fn, strategy, demand, record):
    cluster, model, base, deltas, fwd = tied
    toks = _tokens(fn, seed=7)
    if record:
        cluster.invoke(InvocationRequest(
            function=fn, tokens=_tokens(fn, seed=3),
            options=ColdStartOptions(strategy=strategy, force_cold=True, record=True)))
    res = cluster.invoke(InvocationRequest(
        function=fn, tokens=toks,
        options=ColdStartOptions(strategy=strategy, force_cold=True,
                                 demand_paging=demand)))
    assert res.cold
    params = unflatten_paths({**base, **deltas[fn]})
    want = np.asarray(fwd(params, toks))[:, -1, :8]
    np.testing.assert_array_equal(res.output, want)


def test_record_mode_logs_the_whole_tied_table(tied):
    """REAP's record mode logs a full touch of a tied table, not the
    request's rows, so a replay prefetches every chunk the head reads."""
    cluster, model, base, deltas, _ = tied
    cluster.register_function(FunctionSpec(
        name="head-rec", family=model.cfg.name, delta=deltas["head"]))
    cluster.invoke(InvocationRequest(
        function="head-rec", tokens=_tokens("head", seed=5),
        options=ColdStartOptions(strategy="reap", force_cold=True, record=True)))
    worker = cluster.worker_for("head-rec")
    n = worker.pool.get("head-rec").arrays["embed/table"].meta.num_chunks()
    recorded = {i for p, i in worker.registry.functions["head-rec"].recording.chunks
                if p == "embed/table"}
    assert recorded == set(range(n))
