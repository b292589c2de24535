"""The worker builds each family's parameter template once, at registration.

A request maps the template's paths onto its restored instance; the
template depends only on the family's config, so ``Worker.register_runtime``
builds it with ``Model.param_shapes()`` and every request reads it back.
``Worker.template_builds`` counts the builds: one per registration, however
many requests follow, cold or warm.  The answers do not change: a cold
request's logits equal, byte for byte, the jitted forward over a tree built
from a fresh ``param_shapes()`` on the same restored instance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.snapshot import flatten_pytree
from repro.models import build_model
from repro.serving import ColdStartOptions, FunctionSpec, InvocationRequest, Worker

#: the reduced forms of the two cold-start cells' families: a dense model
#: with its own head, and an SSM whose head is tied to the embedding
FAMILIES = ["stablelm-3b", "mamba2-780m"]
#: a block weight each family's adapter changes
ADAPTER_LEAF = {"stablelm-3b": "blocks/pos0/ffn/w_in",
                "mamba2-780m": "blocks/pos0/w_z"}
HEAD_ROWS = list(range(16, 48))


def _deltas(name, base):
    rng = np.random.default_rng(2)
    w = ADAPTER_LEAF[name]
    table = base["embed/table"].copy()
    table[HEAD_ROWS] += rng.standard_normal(
        (len(HEAD_ROWS), table.shape[1])).astype(table.dtype) * 0.01
    return {"adapter": {w: base[w] + np.float32(0.01)},
            "head": {"embed/table": table}}


def _serve(root, cfg, model):
    worker = Worker(root, chunk_bytes=4096)
    worker.register_runtime(cfg.name, model, model.init(0))
    base = flatten_pytree(jax.tree.map(np.asarray, model.init(0)))
    for fn, delta in _deltas(cfg.name.removesuffix("-smoke"), base).items():
        worker.register_function(FunctionSpec(
            name=fn, family=cfg.name, delta=delta,
            touched_rows={"embed/table": HEAD_ROWS} if fn == "head" else {}))
    return worker


def _invoke(worker, fn, *, cold, seed=0):
    toks = np.random.default_rng(seed).choice(
        np.asarray(HEAD_ROWS), size=(1, 16)).astype(np.int32)
    res = worker.invoke(InvocationRequest(
        function=fn, tokens=toks,
        options=ColdStartOptions(strategy="snapfaas", force_cold=cold)))
    assert res.cold == cold
    return res, toks


def _leaves(tree):
    """``{path: (shape, dtype)}`` of a tree of arrays or ShapeDtypeStructs."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(k.key for k in p): (tuple(x.shape), np.dtype(x.dtype))
            for p, x in flat}


def _fresh_params(model, inst):
    """A fresh template's paths mapped onto ``inst``'s host values."""
    flat = jax.tree_util.tree_flatten_with_path(model.param_shapes())
    return jax.tree_util.tree_unflatten(flat[1], [
        jnp.asarray(inst.value("/".join(k.key for k in p))) for p, _ in flat[0]])


@pytest.fixture(scope="module", params=FAMILIES)
def served(request, tmp_path_factory):
    cfg = reduced(get_config(request.param))
    model = build_model(cfg)
    worker = _serve(str(tmp_path_factory.mktemp(request.param)), cfg, model)
    for fn in ("adapter", "head"):
        for i in range(3):
            _invoke(worker, fn, cold=True, seed=i)
        for i in range(2):
            _invoke(worker, fn, cold=False, seed=10 + i)
    return worker, cfg, model


def test_one_template_build_per_family(served):
    worker, cfg, _ = served
    assert worker.template_builds == {cfg.name: 1}
    _invoke(worker, "adapter", cold=True, seed=20)
    _invoke(worker, "head", cold=False, seed=21)
    assert worker.template_builds == {cfg.name: 1}


@pytest.mark.parametrize("fn", ["adapter", "head"])
def test_request_tree_matches_fresh_template(served, fn):
    worker, _, model = served
    _invoke(worker, fn, cold=True, seed=30)
    inst = worker.pool.get(fn)
    params = worker._params_for(worker.specs[fn], inst)
    assert _leaves(params) == _leaves(model.param_shapes())


@pytest.mark.parametrize("fn", ["adapter", "head"])
def test_cold_logits_equal_forward_over_fresh_template(served, fn):
    worker, cfg, model = served
    res, toks = _invoke(worker, fn, cold=True, seed=40)
    inst = worker.pool.get(fn)
    logits = worker._fwd[cfg.name](_fresh_params(model, inst), jnp.asarray(toks))
    want = np.asarray(logits[:, -1, :8])
    assert res.output.tobytes() == want.tobytes()
    again, _ = _invoke(worker, fn, cold=True, seed=40)
    warm, _ = _invoke(worker, fn, cold=False, seed=40)
    assert again.output.tobytes() == want.tobytes()
    assert warm.output.tobytes() == want.tobytes()


@pytest.mark.parametrize("name", FAMILIES)
def test_reregistration_rebuilds_template(tmp_path, name):
    """A second ``register_runtime`` replaces the family's template with the
    new model's: here a wider vocabulary, so a stale template would hand
    the forward an embedding path of the old shape."""
    cfg = reduced(get_config(name))
    worker = Worker(str(tmp_path / "w"), chunk_bytes=4096)
    worker.register_runtime(cfg.name, build_model(cfg), build_model(cfg).init(0))
    wide = build_model(dataclasses.replace(cfg, vocab_size=2 * cfg.vocab_size))
    worker.register_runtime(cfg.name, wide, wide.init(0))
    assert worker.template_builds == {cfg.name: 2}
    assert _leaves(worker._templates[cfg.name]) == _leaves(wide.param_shapes())
    base = flatten_pytree(jax.tree.map(np.asarray, wide.init(0)))
    worker.register_function(FunctionSpec(
        name="adapter", family=cfg.name, delta=_deltas(name, base)["adapter"]))
    _invoke(worker, "adapter", cold=True)
    params = worker._params_for(worker.specs["adapter"], worker.pool.get("adapter"))
    assert _leaves(params)["embed/table"] == ((2 * cfg.vocab_size, cfg.d_model),
                                              np.dtype(np.float32))
    assert worker.template_builds == {cfg.name: 2}
