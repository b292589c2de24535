"""Public model API: build_model(cfg) → Model with train/prefill/decode entry
points and cache constructors. This is the layer launch/, serving/ and
train/ program against."""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks as blocks_mod
from .config import ModelConfig
from .layers import sinusoidal_positions, softcap
from .transformer import (
    apply_norm,
    apply_stack,
    chunked_cross_entropy,
    init_params,
)

PyTree = Any


@dataclass
class Batch:
    """Training/prefill batch. For [audio]/[vlm] archs the frontend is a stub:
    ``prefix_embeds`` carries precomputed frame/patch embeddings."""

    tokens: jax.Array                     # (b, s) int32
    labels: Optional[jax.Array] = None    # (b, s) int32; -1 ignored
    prefix_embeds: Optional[jax.Array] = None  # (b, p, D)


class Model:
    def __init__(self, cfg: ModelConfig, *, remat: bool = False,
                 loss_chunk: int = 1024, remat_group: int = 1):
        self.cfg = cfg
        self.plan = blocks_mod.build_plan(cfg)
        self.remat = remat
        self.loss_chunk = loss_chunk
        self.remat_group = remat_group

    # -- parameters ---------------------------------------------------------

    def init(self, seed: int = 0) -> PyTree:
        return init_params(self.cfg, seed)

    def param_shapes(self) -> PyTree:
        return jax.eval_shape(lambda: self.init())

    # -- embedding helpers ----------------------------------------------------

    def _embed(self, params, tokens: jax.Array) -> jax.Array:
        from repro.distrib.act import shard

        h = jnp.take(params["embed"]["table"], tokens, axis=0)
        if self.cfg.embed_scale:
            h = h * jnp.asarray(np.sqrt(self.cfg.d_model), h.dtype)
        return shard(h, "batch", "seq", "embed")

    def _logits_head(self, params, h: jax.Array) -> jax.Array:
        W = (params["embed"]["table"] if self.cfg.tie_embeddings
             else params["lm_head"]["w"])
        h = h.astype(W.dtype)  # residual stream may be f32; matmul in bf16
        if self.cfg.tie_embeddings:
            logits = jnp.einsum("bld,vd->blv", h, W,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bld,dv->blv", h, W,
                                preferred_element_type=jnp.float32)
        return softcap(logits, self.cfg.final_logit_softcap)

    # -- encoder (whisper) ----------------------------------------------------

    def _encode(self, params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        pos_tab = jnp.asarray(sinusoidal_positions(frames.shape[1], cfg.d_model),
                              frames.dtype)
        h = frames + pos_tab[None]
        from .config import LayerKind
        h, _, _ = apply_stack(
            cfg, (LayerKind("attn", "mlp"),), params["enc"]["blocks"], h,
            positions=jnp.arange(frames.shape[1]), causal=False,
            remat=self.remat,
        )
        return apply_norm(h, params["enc"]["final_norm"], cfg)

    # -- full-sequence forward ----------------------------------------------

    def forward(self, params, batch: Batch) -> jax.Array:
        """Full-sequence final hidden states (b, s_text, D)."""
        cfg = self.cfg
        tokens = batch.tokens
        if cfg.is_encoder_decoder:
            assert batch.prefix_embeds is not None, "enc-dec needs frame embeds"
            enc = self._encode(params, batch.prefix_embeds)
            h = self._embed(params, tokens)
            pos_tab = jnp.asarray(sinusoidal_positions(tokens.shape[1], cfg.d_model), h.dtype)
            h = h + pos_tab[None]
            from .config import LayerKind
            h, _, _ = apply_stack(
                cfg, (LayerKind("attn", "mlp"),), params["blocks"], h,
                positions=jnp.arange(tokens.shape[1]), cross_states=enc,
                remat=self.remat,
            )
            return apply_norm(h, params["final_norm"], cfg)

        h = self._embed(params, tokens)
        prefix_len = 0
        if batch.prefix_embeds is not None:  # vlm prefix (paligemma)
            h = jnp.concatenate([batch.prefix_embeds.astype(h.dtype), h], axis=1)
            prefix_len = batch.prefix_embeds.shape[1]
        positions = jnp.arange(h.shape[1])
        h, _, aux = apply_stack(
            self.cfg, self.plan.kinds, params["blocks"], h,
            positions=positions, prefix_len=prefix_len, remat=self.remat,
            remat_group=self.remat_group,
        )
        self._last_aux = aux
        h = apply_norm(h, params["final_norm"], self.cfg)
        if prefix_len:
            h = h[:, prefix_len:, :]
        return h

    def logits(self, params, batch: Batch) -> jax.Array:
        return self._logits_head(params, self.forward(params, batch))

    def loss(self, params, batch: Batch, *, aux_weight: float = 0.01) -> jax.Array:
        h = self.forward(params, batch)
        assert batch.labels is not None
        table = (
            params["embed"]["table"] if self.cfg.tie_embeddings else params["lm_head"]["w"]
        )
        ce = chunked_cross_entropy(
            h, table, batch.labels,
            final_softcap=self.cfg.final_logit_softcap,
            chunk=self.loss_chunk,
            transpose_head=not self.cfg.tie_embeddings,
        )
        aux = getattr(self, "_last_aux", None)
        if aux is not None and self.cfg.num_experts:
            ce = ce + aux_weight * aux / max(1, self.cfg.num_layers)
        return ce

    # -- caches ----------------------------------------------------------------

    def init_cache(self, batch: int, cache_len: int, dtype=None, enc_len: int = 0) -> PyTree:
        """Zeroed cache pytree shaped for decode_step (also used as
        ShapeDtypeStruct template by the dry-run)."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        n = self.plan.n_repeat if not cfg.is_encoder_decoder else cfg.num_decoder_layers
        cache: Dict[str, Any] = {}
        if cfg.is_encoder_decoder:
            cache["pos0"] = {
                "k": jnp.zeros((n, batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((n, batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
                "ck": jnp.zeros((n, batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dt),
                "cv": jnp.zeros((n, batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dt),
            }
            return cache
        for i, kind in enumerate(self.plan.kinds):
            if kind.mixer == "attn":
                cache[f"pos{i}"] = {
                    "k": jnp.zeros((n, batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
                    "v": jnp.zeros((n, batch, cache_len, cfg.num_kv_heads, cfg.head_dim), dt),
                }
            else:
                ch = cfg.d_inner + 2 * cfg.ssm_state
                cache[f"pos{i}"] = {
                    "conv": jnp.zeros((n, batch, cfg.ssm_conv - 1, ch), dt),
                    "ssm": jnp.zeros(
                        (n, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                        jnp.float32,
                    ),
                }
        return cache

    # -- prefill ---------------------------------------------------------------

    def prefill(self, params, batch: Batch, cache_len: int) -> Tuple[jax.Array, PyTree]:
        """Run the full prompt, return (last-token logits, cache)."""
        cfg = self.cfg
        tokens = batch.tokens
        if cfg.is_encoder_decoder:
            enc = self._encode(params, batch.prefix_embeds)
            h = self._embed(params, tokens)
            pos_tab = jnp.asarray(sinusoidal_positions(tokens.shape[1], cfg.d_model), h.dtype)
            h = h + pos_tab[None]
            from .config import LayerKind
            h, caches, _ = apply_stack(
                cfg, (LayerKind("attn", "mlp"),), params["blocks"], h,
                positions=jnp.arange(tokens.shape[1]), cross_states=enc,
                make_cache=True, cache_len=cache_len, remat=self.remat,
            )
            h = apply_norm(h, params["final_norm"], cfg)
            return self._logits_head(params, h[:, -1:, :]), caches

        h = self._embed(params, tokens)
        prefix_len = 0
        if batch.prefix_embeds is not None:
            h = jnp.concatenate([batch.prefix_embeds.astype(h.dtype), h], axis=1)
            prefix_len = batch.prefix_embeds.shape[1]
        h, caches, _ = apply_stack(
            cfg, self.plan.kinds, params["blocks"], h,
            positions=jnp.arange(h.shape[1]), prefix_len=prefix_len,
            make_cache=True, cache_len=cache_len, remat=self.remat,
        )
        h = apply_norm(h, params["final_norm"], cfg)
        return self._logits_head(params, h[:, -1:, :]), caches

    # -- decode -----------------------------------------------------------------

    def decode_step(
        self, params, cache: PyTree, tokens: jax.Array, pos: jax.Array
    ) -> Tuple[jax.Array, PyTree]:
        """One decode step. tokens (b,) int32, pos scalar int32 (aligned
        batch decode; per-request offsets live in the serving layer).
        Returns (logits (b, V), new cache)."""
        cfg = self.cfg
        h = self._embed(params, tokens[:, None])
        if cfg.is_encoder_decoder:
            cache_len = cache["pos0"]["k"].shape[2]
            pos_tab = jnp.asarray(sinusoidal_positions(cache_len, cfg.d_model), h.dtype)
            h = h + jax.lax.dynamic_slice_in_dim(pos_tab, pos, 1, 0)[None]
            from .config import LayerKind
            kinds = (LayerKind("attn", "mlp"),)
            # cross_states flag: any non-None sentinel routes to cached ck/cv
            h, new_cache, _ = apply_stack(
                cfg, kinds, params["blocks"], h,
                positions=jnp.arange(1), cache=cache, decode=True, pos=pos,
                cross_states=h,  # sentinel; decode path reads cache["ck"/"cv"]
            )
        else:
            h, new_cache, _ = apply_stack(
                cfg, self.plan.kinds, params["blocks"], h,
                positions=jnp.arange(1), cache=cache, decode=True, pos=pos,
            )
        h = apply_norm(h, params["final_norm"], cfg)
        logits = self._logits_head(params, h)
        return logits[:, 0, :], new_cache


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
