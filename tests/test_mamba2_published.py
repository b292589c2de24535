"""mamba2-780m at its published configuration: the benchmark's file against
the program, the float32 residual stream, and the program's logits against
the plain float32 reference at tiny widths.

The residual stream is carried in float32 between layers when the config
says ``residual_in_fp32`` (mamba_ssm's ``fused_add_norm`` with
``residual_in_fp32``); each layer's norm and mixer still compute in the
config's dtype.  Every other config keeps the default, and its forward is
the plain scan it was.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import check, harness, weights  # noqa: E402
from repro.configs import get_config, list_archs, reduced  # noqa: E402
from repro.models import Batch, build_model  # noqa: E402
from repro.models.transformer import apply_layer, apply_norm  # noqa: E402

PUBLISHED = REPO / "bench" / "configs" / "mamba2-780m.published.json"

#: tiny widths of the published file: the program's and the reference's
TINY = {"d_model": 64, "n_layer": 2, "vocab_size": 500, "padded_vocab_size": 512}
TINY_PROGRAM = {"num_layers": 2, "d_model": 64, "vocab_size": 512,
                "ssm_state": 16, "ssm_head_dim": 16}
#: the widest gap of 4 prompts on 3 seeds read 0.0265-0.0294 on the CPU
#: (bf16 weights and layer compute against the float32 reference;
#: 0.0317-0.0327 with the residual in bf16), the fp8 control 0.178-0.214:
#: the limit sits between, with room on both sides
TINY_GAP = 0.07


def _tiny(name, **kw):
    """The registered config at smoke widths, in the published bfloat16."""
    return dataclasses.replace(reduced(get_config(name)), dtype="bfloat16", **kw)


def _published():
    return json.loads(PUBLISHED.read_text())


def test_published_file_gives_every_published_key():
    cfg = _published()
    assert cfg["reduced"] == {}
    mc = harness.program_config(cfg)
    assert (mc.num_layers, mc.d_model, mc.ssm_state, mc.ssm_head_dim, mc.ssm_expand,
            mc.ssm_conv, mc.ssm_chunk) == (48, 1536, 128, 64, 2, 4, 256)
    assert mc.d_inner == 3072 and mc.ssm_heads == 48
    assert mc.tie_embeddings and mc.residual_in_fp32 and mc.dtype == "bfloat16"
    assert mc.rms_norm_eps == cfg["layer"]["norm_eps"] == 1e-5
    # 50,277 tokens padded to a multiple of 16
    assert mc.vocab_size == cfg["padded_vocab_size"] == 50288
    assert cfg["vocab_size"] == 50277 and 50288 % cfg["pad_vocab_size_multiple"] == 0


@pytest.mark.parametrize("key,value", [("residual_in_fp32", False),
                                       ("padded_vocab_size", 50280),
                                       ("layer.norm_eps", 1e-6)])
def test_published_file_refuses_a_program_that_departs(key, value):
    cfg = _published()
    if key.startswith("layer."):
        cfg["layer"] = dict(cfg["layer"], **{key.split(".", 1)[1]: value})
    else:
        cfg[key] = value
    with pytest.raises(ValueError):
        harness.program_config(cfg)


@pytest.mark.parametrize("name", list_archs())
def test_other_families_keep_the_defaults(name):
    """Only mamba2-780m states a float32 residual and RMSNorm eps 1e-5;
    every other family keeps the stream and the eps it had."""
    cfg = get_config(name)
    want = (True, 1e-5) if cfg.name == "mamba2-780m" else (False, 1e-6)
    assert (cfg.residual_in_fp32, cfg.rms_norm_eps) == want


def _scan_carry_dtypes(cfg):
    model = build_model(cfg)
    params = model.param_shapes()
    toks = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: model.logits(p, Batch(tokens=t)))(params, toks)
    scan = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan")
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    return [a.dtype for a in scan.params["jaxpr"].in_avals[n_consts:n_consts + n_carry]
            if a.shape == (1, 16, cfg.d_model)]


@pytest.mark.parametrize("override,carry", [({}, jnp.float32),
                                            ({"residual_in_fp32": False}, jnp.bfloat16)])
def test_residual_carry_between_layers_follows_the_config(override, carry):
    assert _scan_carry_dtypes(_tiny("mamba2-780m", **override)) == [carry]


def _plain_scan_logits(model, params, tokens):
    """The forward as a plain scan over layers whose carry keeps the
    embedding's dtype: the stack before ``residual_in_fp32`` existed."""
    cfg = model.cfg
    positions = jnp.arange(tokens.shape[1])

    def body(carry, bp):
        h, aux = carry
        for i, kind in enumerate(model.plan.kinds):
            h, _, a = apply_layer(cfg, kind, bp[f"pos{i}"], h, positions=positions)
            aux = aux + a
        return (h, aux), None

    h = model._embed(params, tokens)
    (h, _), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), params["blocks"])
    return model._logits_head(params, apply_norm(h, params["final_norm"], cfg))


def test_stablelm_forward_is_bit_identical_at_the_default():
    cfg = _tiny("stablelm-3b")
    assert not cfg.residual_in_fp32 and _scan_carry_dtypes(cfg) == [jnp.bfloat16]
    model = build_model(cfg)
    params = model.init(0)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16)),
                       jnp.int32)
    got = jax.jit(lambda p, t: model.logits(p, Batch(tokens=t)))(params, toks)
    want = jax.jit(lambda p, t: _plain_scan_logits(model, p, t))(params, toks)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def _tiny_published():
    cfg = dict(_published(), **TINY, program=TINY_PROGRAM)
    cfg["layer"] = dict(cfg["layer"], d_state=16, headdim=16)
    return cfg


def test_program_logits_match_the_reference_at_tiny_widths():
    cfg = _tiny_published()
    mc = harness.program_config(cfg)
    assert mc.residual_in_fp32 and mc.tie_embeddings
    model = build_model(mc)
    ref = harness.load_module(PUBLISHED.parent / f"{cfg['reference']}.py", "ref_mamba2_tiny")
    flat = weights.make_base(weights.flatten(model.param_shapes()), cfg["init"], 2**31 + 15)
    params = weights.unflatten(flat)
    fwd = jax.jit(lambda p, t: model.logits(p, Batch(tokens=t)))
    ref_fwd = jax.jit(lambda p, t: ref.forward_last(p, t, cfg))
    rng = np.random.default_rng(0)
    gaps = []
    for _ in range(4):
        toks = rng.integers(0, cfg["vocab_size"], (1, 32)).astype(np.int32)
        got = np.asarray(fwd(params, toks))[0, -1]
        gaps.append(check.logit_gap(got, np.asarray(ref_fwd(params, toks[0]))))
    assert max(gaps) < TINY_GAP, gaps
