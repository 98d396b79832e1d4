"""Model driver: block composition, scan-over-groups, remat, train loss,
prefill and decode.

Layer stack = ``pattern_repeats`` x ``block_pattern`` (scanned, params stacked
on a leading repeat axis) + ``remainder_pattern`` (unscanned).  Every block
kind exposes (init, forward, init_cache, decode_step); MoE replaces the MLP
in attention blocks when ``cfg.is_moe``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import mlp as MLP
from repro.models import moe as MOE
from repro.models import recurrent as REC
from repro.models import xlstm as XL
from repro.models.config import ModelConfig
from repro.models.pspec import shard

Params = dict


# ---------------------------------------------------------------------------
# per-block init / forward / cache / decode
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str) -> Params:
    ks = jax.random.split(key, 2)
    if kind in ("attn", "local"):
        p = {"ln1": L.norm_params(cfg.d_model, cfg.norm),
             "attn": ATT.init(ks[0], cfg),
             "ln2": L.norm_params(cfg.d_model, cfg.norm)}
        if cfg.is_moe:
            p["moe"] = MOE.init(ks[1], cfg)
        else:
            p["mlp"] = MLP.init(ks[1], cfg)
        return p
    if kind == "rglru":
        return {"ln1": L.norm_params(cfg.d_model, cfg.norm),
                "rec": REC.init(ks[0], cfg),
                "ln2": L.norm_params(cfg.d_model, cfg.norm),
                "mlp": MLP.init(ks[1], cfg)}
    if kind == "mlstm":
        return {"ln1": L.norm_params(cfg.d_model, cfg.norm),
                "cell": XL.mlstm_init(ks[0], cfg)}
    if kind == "slstm":
        ffn_cfg = {"d_ff": int(cfg.d_model * cfg.slstm_proj_factor)}
        return {"ln1": L.norm_params(cfg.d_model, cfg.norm),
                "cell": XL.slstm_init(ks[0], cfg),
                "ln2": L.norm_params(cfg.d_model, cfg.norm),
                "ffn": _plain_mlp_init(ks[1], cfg, ffn_cfg["d_ff"])}
    raise ValueError(kind)


def _plain_mlp_init(key, cfg: ModelConfig, d_ff: int) -> Params:
    ks = jax.random.split(key, 2)
    pd = jnp.dtype(cfg.param_dtype)
    return {"wi": {"w": L.dense_init(ks[0], cfg.d_model, d_ff, pd)},
            "wo": {"w": L.dense_init(ks[1], d_ff, cfg.d_model, pd)}}


def _plain_mlp(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return L.dense(p["wo"], jax.nn.gelu(L.dense(p["wi"], x)))


def _block_forward(p: Params, cfg: ModelConfig, kind: str, x: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Full-sequence block.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local"):
        h = ATT.forward(p["attn"], cfg, L.apply_norm(p["ln1"], x, cfg.norm),
                        local=(kind == "local"))
        x = x + h
        u = L.apply_norm(p["ln2"], x, cfg.norm)
        if cfg.is_moe:
            m, aux = MOE.forward(p["moe"], cfg, u)
        else:
            m = MLP.forward(p["mlp"], cfg, u)
        return x + m, aux
    if kind == "rglru":
        x = x + REC.forward(p["rec"], cfg, L.apply_norm(p["ln1"], x, cfg.norm))
        x = x + MLP.forward(p["mlp"], cfg, L.apply_norm(p["ln2"], x, cfg.norm))
        return x, aux
    if kind == "mlstm":
        return x + XL.mlstm_forward(p["cell"], cfg,
                                    L.apply_norm(p["ln1"], x, cfg.norm)), aux
    if kind == "slstm":
        x = x + XL.slstm_forward(p["cell"], cfg,
                                 L.apply_norm(p["ln1"], x, cfg.norm))
        x = x + _plain_mlp(p["ffn"], cfg, L.apply_norm(p["ln2"], x, cfg.norm))
        return x, aux
    raise ValueError(kind)


def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int):
    if kind == "attn":
        return ATT.init_cache(cfg, batch, max_len, local=False)
    if kind == "local":
        return ATT.init_cache(cfg, batch, max_len, local=True)
    if kind == "rglru":
        return REC.init_state(cfg, batch)
    if kind == "mlstm":
        return XL.mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return XL.slstm_init_state(cfg, batch)
    raise ValueError(kind)


def _block_decode(p: Params, cfg: ModelConfig, kind: str, x: jax.Array,
                  cache, index) -> tuple[jax.Array, Any]:
    if kind in ("attn", "local"):
        h, cache_attn = ATT.decode_step(
            p["attn"], cfg, L.apply_norm(p["ln1"], x, cfg.norm), cache, index,
            local=(kind == "local"))
        x = x + h
        u = L.apply_norm(p["ln2"], x, cfg.norm)
        if cfg.is_moe:
            m, _ = MOE.forward(p["moe"], cfg, u, decode=True)
        else:
            m = MLP.forward(p["mlp"], cfg, u)
        return x + m, cache_attn
    if kind == "rglru":
        h, st = REC.decode_step(p["rec"], cfg,
                                L.apply_norm(p["ln1"], x, cfg.norm), cache)
        x = x + h
        x = x + MLP.forward(p["mlp"], cfg, L.apply_norm(p["ln2"], x, cfg.norm))
        return x, st
    if kind == "mlstm":
        h, st = XL.mlstm_decode_step(p["cell"], cfg,
                                     L.apply_norm(p["ln1"], x, cfg.norm), cache)
        return x + h, st
    if kind == "slstm":
        h, st = XL.slstm_decode_step(p["cell"], cfg,
                                     L.apply_norm(p["ln1"], x, cfg.norm), cache)
        x = x + h
        x = x + _plain_mlp(p["ffn"], cfg, L.apply_norm(p["ln2"], x, cfg.norm))
        return x, st
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    pd = jnp.dtype(cfg.param_dtype)
    params: Params = {}
    if cfg.is_decoder or cfg.family == "vlm":
        params["embed"] = L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model, pd)
    if cfg.frontend:
        params["frontend"] = {
            "w": L.dense_init(keys[1], cfg.frontend_dim, cfg.d_model, pd)}

    def group_init(k):
        ks = jax.random.split(k, len(cfg.block_pattern))
        return {f"b{i}": _block_init(ks[i], cfg, kind)
                for i, kind in enumerate(cfg.block_pattern)}

    if cfg.pattern_repeats > 0:
        gkeys = jax.random.split(keys[2], cfg.pattern_repeats)
        params["groups"] = jax.vmap(group_init)(gkeys)
    rest_keys = jax.random.split(keys[3], max(1, len(cfg.remainder_pattern)))
    params["rest"] = [
        _block_init(rest_keys[i], cfg, kind)
        for i, kind in enumerate(cfg.remainder_pattern)
    ]
    params["ln_f"] = L.norm_params(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["head"] = {"w": L.dense_init(keys[4], cfg.d_model,
                                            cfg.padded_vocab, pd)}
    return params


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------

def embed_inputs(params: Params, cfg: ModelConfig, *,
                 tokens: jax.Array | None = None,
                 features: jax.Array | None = None) -> jax.Array:
    """Token embeddings, stub-frontend features, or both (VLM prepends)."""
    parts = []
    if features is not None:
        f = features.astype(cfg.activation_dtype)
        parts.append(L.dense(params["frontend"], f))
    if tokens is not None:
        emb = params["embed"].astype(cfg.activation_dtype)
        parts.append(emb[tokens])
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return shard(x, "batch", "act_seq", None)


def forward_hidden(params: Params, cfg: ModelConfig, x: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Run the block stack.  Returns (hidden, total aux loss).

    Hierarchical remat: the scan body (one pattern group) is checkpointed
    *and* every block inside it is checkpointed again.  Forward stores only
    group-boundary activations; the backward pass recomputes one group, which
    in turn stores only block boundaries and recomputes one block's internals
    (attention online-softmax state, mLSTM chunk carries) at a time — the
    difference between 159 GB/chip and fitting in HBM for the xLSTM cell
    (EXPERIMENTS.md SDry-run)."""
    aux_total = jnp.zeros((), jnp.float32)

    def block_fn(kind):
        def fn(p, x):
            # The barrier pins the bf16 residual read inside the backward
            # loop: without it XLA hoists the first f32 upcast (the norm)
            # out of the loop and bulk-converts the whole (L, B, S, d)
            # residual stack to f32 — a 2x memory pessimization measured at
            # +26 GB/chip on qwen2-7b.
            x = jax.lax.optimization_barrier(x)
            return _block_forward(p, cfg, kind, x)
        if cfg.remat:
            fn = jax.checkpoint(fn, prevent_cse=False)
        return fn

    def scan_body(carry, group_params):
        x, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            x, a = block_fn(kind)(group_params[f"b{i}"], x)
            # seq-shard the saved boundary activation (Megatron-SP)
            x = shard(x, "batch", "act_seq", None)
            aux = aux + a
        return (x, aux), None

    body = scan_body
    if cfg.remat:
        body = jax.checkpoint(scan_body, prevent_cse=False)
    if cfg.pattern_repeats > 0:
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["groups"])
    for i, kind in enumerate(cfg.remainder_pattern):
        x, a = block_fn(kind)(params["rest"][i], x)
        aux_total = aux_total + a
    return x, aux_total


def logits_fn(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = L.apply_norm(params["ln_f"], x, cfg.norm)
    if cfg.tie_embeddings:
        w = params["embed"].astype(x.dtype).T
        logits = x @ w
    else:
        logits = L.dense(params["head"], x)
    if cfg.padded_vocab != cfg.vocab_size:
        # mask the sharding-padding rows (elementwise — keeps vocab sharded)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                              logits.ndim - 1)
        logits = jnp.where(vocab_iota < cfg.vocab_size, logits, -1e30)
    return shard(logits, "batch", "seq", "vocab")


def loss_fn(params: Params, cfg: ModelConfig, batch: dict) -> tuple[jax.Array, dict]:
    """batch keys: tokens? features? labels, mask? (all batch-major)."""
    x = embed_inputs(params, cfg,
                     tokens=batch.get("tokens"),
                     features=batch.get("features"))
    x, aux = forward_hidden(params, cfg, x)
    logits = logits_fn(params, cfg, x)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:
        # VLM: loss only over the trailing text positions
        logits = logits[:, -labels.shape[1]:]
    ce = L.cross_entropy(logits, labels, batch.get("mask"))
    loss = ce + cfg.router_aux_weight * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    def group_cache(_):
        return {f"b{i}": _block_cache(cfg, kind, batch, max_len)
                for i, kind in enumerate(cfg.block_pattern)}

    caches: dict = {"rest": [
        _block_cache(cfg, kind, batch, max_len)
        for kind in cfg.remainder_pattern]}
    if cfg.pattern_repeats > 0:
        one = group_cache(None)
        caches["groups"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.pattern_repeats,) + a.shape),
            one)
    return caches


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                caches: dict, index: jax.Array) -> tuple[jax.Array, dict]:
    """One decoding step for the whole stack.  tokens: (B, 1) int32."""
    x = embed_inputs(params, cfg, tokens=tokens)

    def scan_body(x, inp):
        group_params, group_caches = inp
        new = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, new[f"b{i}"] = _block_decode(group_params[f"b{i}"], cfg, kind,
                                            x, group_caches[f"b{i}"], index)
        return x, new

    new_caches: dict = {"rest": []}
    if cfg.pattern_repeats > 0:
        if cfg.scan_layers_decode:
            x, new_groups = jax.lax.scan(scan_body, x,
                                         (params["groups"], caches["groups"]))
            new_caches["groups"] = new_groups
        else:
            # unrolled: each layer's cache slice updates in place (dus on the
            # stacked buffer aliases; no whole-cache copy per token)
            new_groups = caches["groups"]
            for g in range(cfg.pattern_repeats):
                gp = jax.tree.map(lambda a: a[g], params["groups"])
                gc = jax.tree.map(lambda a: a[g], new_groups)
                x, gc_new = scan_body(x, (gp, gc))
                new_groups = jax.tree.map(
                    lambda full, new: jax.lax.dynamic_update_index_in_dim(
                        full, new.astype(full.dtype), g, 0),
                    new_groups, gc_new)
            new_caches["groups"] = new_groups
    for i, kind in enumerate(cfg.remainder_pattern):
        x, c = _block_decode(params["rest"][i], cfg, kind, x,
                             caches["rest"][i], index)
        new_caches["rest"].append(c)
    logits = logits_fn(params, cfg, x)
    return logits[:, 0], new_caches
