"""Model assembly: embedding, scanned layer stacks, loss head, decode paths.

Homogeneous layer stacks are scanned (`lax.scan` over parameters stacked on a
leading L axis) — keeps HLO size O(1) in depth, which keeps 512-device AOT
compiles fast and lets the TaxoNN engine express its per-layer fused update
as a scan carry.

Families:
  dense/moe : embed -> L x transformer_block -> norm -> CE head
  vlm       : [patch_embeds ; text embeds] -> dense stack (loss on text)
  ssm       : embed -> L x mamba_block -> norm -> CE head
  hybrid    : embed -> G x (shared_attn_block ; K x mamba_block) -> ...
  encdec    : frames -> enc stack ; tokens -> dec stack(cross=enc) -> CE head
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.api import constrain
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.util.scan import xscan

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _stacked_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    D, V = cfg.d_model, cfg.vocab_size
    params: dict = {
        "embed": jax.random.normal(keys[0], (V, D), jnp.float32) * D ** -0.5,
        "final_norm": L.init_norm(D, cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (D, V), jnp.float32) * D ** -0.5

    if cfg.family in ("dense", "moe", "vlm"):
        params["blocks"] = _stacked_init(
            keys[2], cfg.num_layers, lambda k: B.init_transformer_block(k, cfg))
        if cfg.family == "vlm":
            params["mm_proj"] = jax.random.normal(keys[3], (D, D), jnp.float32) * D ** -0.5
    elif cfg.family == "ssm":
        params["blocks"] = _stacked_init(
            keys[2], cfg.num_layers, lambda k: B.init_mamba_block(k, cfg))
    elif cfg.family == "hybrid":
        G, K = hybrid_groups(cfg)
        flat = _stacked_init(keys[2], G * K, lambda k: B.init_mamba_block(k, cfg))
        params["blocks"] = jax.tree.map(
            lambda x: x.reshape((G, K) + x.shape[1:]), flat)
        params["shared_attn"] = B.init_transformer_block(keys[3], cfg)
    elif cfg.family == "encdec":
        params["enc_blocks"] = _stacked_init(
            keys[2], cfg.num_encoder_layers,
            lambda k: B.init_transformer_block(k, cfg))
        params["enc_norm"] = L.init_norm(D, cfg)
        params["blocks"] = _stacked_init(
            keys[3], cfg.num_layers, lambda k: B.init_decoder_block(k, cfg))
    else:
        raise ValueError(cfg.family)
    return params


# How each family's main stack consumes operands that are not the layer's
# own parameters or the flowing activation — the contract the stage-sharded
# pipeline path (core/steps.py) uses to replicate or slice them:
#   "none"       self-contained per-layer bodies (dense/moe/vlm/ssm)
#   "weights"    a weight-tied block applied by every unit (hybrid's shared
#                attn): broadcast-class — replicated to every stage, layer-
#                quantized in place, gradient summed across stages by the
#                vjp of the broadcast
#   "activation" a full-batch activation fanned out to every layer (encdec's
#                encoder output): broadcast-class, but batch-indexed — each
#                stage slices the microbatch it is processing
# (moe's load-balance aux loss is the reduce-class counterpart: a per-layer
# side OUTPUT accumulated across stages and summed after the drain.)
SHARED_OPERAND_KIND = {
    "dense": "none", "moe": "none", "vlm": "none", "ssm": "none",
    "hybrid": "weights", "encdec": "activation",
}


def hybrid_groups(cfg: ModelConfig) -> tuple[int, int]:
    """Zamba2-style grouping: shared attn block applied every `attn_every`
    mamba layers -> G groups of K layers."""
    K = cfg.attn_every
    assert cfg.num_layers % K == 0, (cfg.num_layers, K)
    return cfg.num_layers // K, K


# ---------------------------------------------------------------------------
# Embedding & positions
# ---------------------------------------------------------------------------

def _sinusoid(t: int, d: int, offset=0) -> Array:
    pos = (jnp.arange(t, dtype=jnp.float32) + offset)[:, None]  # offset may be traced
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((t, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    pe = pe.at[:, 1::2].set(jnp.cos(angle))
    return pe


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def embed_input(params, cfg: ModelConfig, batch: dict):
    """Returns (x0 [B,T,D], positions [B,T])."""
    dt = compute_dtype(cfg)
    tokens = batch["tokens"]
    # cast BEFORE the gather: with a vocab-sharded table the lookup psum
    # then runs at compute precision (half the collective bytes of f32)
    x = params["embed"].astype(dt)[tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(dt) @ params["mm_proj"].astype(dt)
        x = jnp.concatenate([patches, x], axis=1)
    if cfg.family == "encdec":
        x = x + _sinusoid(x.shape[1], cfg.d_model).astype(dt)
    b, t = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    return constrain(x, "btd"), positions


# ---------------------------------------------------------------------------
# Layer stacks (full sequence)
# ---------------------------------------------------------------------------

def apply_stack(params, cfg: ModelConfig, x: Array, positions: Array,
                enc_out: Optional[Array] = None):
    """Run the main stack. Returns (x_final, aux_loss)."""
    if cfg.family in ("dense", "moe", "vlm"):
        def body(h, p):
            h2, aux = B.transformer_block(p, h, cfg, positions)
            return h2, aux
        x, auxs = xscan(body, x, params["blocks"])
        return x, jnp.sum(auxs)

    if cfg.family == "ssm":
        def body(h, p):
            h2, aux = B.mamba_block(p, h, cfg, positions)
            return h2, aux
        x, auxs = xscan(body, x, params["blocks"])
        return x, jnp.sum(auxs)

    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def group(h, gp):
            h, _ = B.transformer_block(shared, h, cfg, positions)

            def inner(hh, p):
                h2, aux = B.mamba_block(p, hh, cfg, positions)
                return h2, aux
            h, _ = xscan(inner, h, gp)
            return h, jnp.float32(0.0)
        x, _ = xscan(group, x, params["blocks"])
        return x, jnp.float32(0.0)

    if cfg.family == "encdec":
        assert enc_out is not None

        def body(h, p):
            h2, aux = B.decoder_block(p, h, cfg, positions, enc_out)
            return h2, aux
        x, auxs = xscan(body, x, params["blocks"])
        return x, jnp.sum(auxs)

    raise ValueError(cfg.family)


def encode(params, cfg: ModelConfig, frames: Array) -> Array:
    """Whisper encoder over precomputed (stub) frame embeddings [B,S,D]."""
    dt = compute_dtype(cfg)
    x = frames.astype(dt) + _sinusoid(frames.shape[1], cfg.d_model).astype(dt)
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(h, p):
        h2, aux = B.transformer_block(p, h, cfg, positions, causal=False)
        return h2, aux
    x, _ = xscan(body, x, params["enc_blocks"])
    return L.apply_norm(params["enc_norm"], x, cfg)


# ---------------------------------------------------------------------------
# Loss head (chunked cross-entropy: [B,T,V] never materialised)
# ---------------------------------------------------------------------------

def head_weight(params, cfg: ModelConfig) -> Array:
    if cfg.tie_embeddings:
        return params["embed"].T  # [D, V]
    return params["lm_head"]


def ce_loss_head(params, cfg: ModelConfig, x: Array, labels: Array):
    """Chunked CE over the sequence axis.  labels: [B,T], -1 = ignore.
    Logits for each chunk are (re)computed inside a remat'd scan body, so the
    full [B,T,V] tensor never exists — fwd or bwd.  Returns (loss, metrics)."""
    return ce_from_weight(head_weight(params, cfg), cfg, x, labels)


def ce_from_weight(w: Array, cfg: ModelConfig, x: Array, labels: Array):
    """CE head given an explicit [D, V] output weight (used by the TaxoNN
    engine, which differentiates the head separately)."""
    with jax.named_scope("head_loss"):
        return _chunked_ce(w, cfg, x, labels)


def _chunked_ce(w: Array, cfg: ModelConfig, x: Array, labels: Array):
    bsz, t, d = x.shape
    c = min(cfg.logit_chunk, t)
    n = (t + c - 1) // c
    pad = n * c - t
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = x.reshape(bsz, n, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(bsz, n, c).transpose(1, 0, 2)

    from repro.dist.api import perf_opt  # local import: avoid cycle
    ce_bf16 = perf_opt("ce_bf16")

    @jax.checkpoint
    def body(carry, xs):
        tot, cnt = carry
        xch, lch = xs
        raw = xch @ w.astype(xch.dtype)
        # §Perf "ce_bf16": keep the [B,C,V] logits in bf16 (halves the CE
        # head's HBM bytes); max in bf16, exp in bf16, SUM accumulated f32.
        logits = constrain(raw if ce_bf16 else raw.astype(jnp.float32), "btv")
        m = jnp.max(logits, axis=-1, keepdims=True)
        sumexp = jnp.sum(jnp.exp(logits - m), axis=-1, dtype=jnp.float32)
        lse = jnp.log(sumexp) + m[..., 0].astype(jnp.float32)
        # vocab-parallel target pick: masked reduction instead of gather —
        # with V sharded on "model" this is collective-free (the gather
        # form all-gathers the full [B,C,V] logits across TP shards)
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        mask = iota == jnp.maximum(lch, 0)[..., None]
        tgt = jnp.sum(jnp.where(mask, logits, 0).astype(jnp.float32), axis=-1)
        valid = (lch >= 0).astype(jnp.float32)
        return (tot + jnp.sum((lse - tgt) * valid), cnt + jnp.sum(valid)), None

    (tot, cnt), _ = xscan(body, (jnp.float32(0.0), jnp.float32(0.0)), (xc, lc))
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss, {"loss": loss, "tokens": cnt}


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------

AUX_COEF = 0.01  # MoE load-balance coefficient


def loss_fn(params, cfg: ModelConfig, batch: dict):
    """Autodiff-path training loss (the jax.grad baseline the TaxoNN engine
    is validated against)."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"])
    x, positions = embed_input(params, cfg, batch)
    x, aux = apply_stack(params, cfg, x, positions, enc_out)
    x = L.apply_norm(params["final_norm"], x, cfg)
    labels = batch["labels"]
    if cfg.family == "vlm":  # loss over text positions only
        x = x[:, batch["patch_embeds"].shape[1]:, :]
    loss, metrics = ce_loss_head(params, cfg, x, labels)
    total = loss + AUX_COEF * aux
    metrics["aux"] = aux
    return total, metrics


def forward_hidden(params, cfg: ModelConfig, batch: dict) -> Array:
    """Forward to final hidden states (prefill / inference)."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["frames"])
    x, positions = embed_input(params, cfg, batch)
    x, _ = apply_stack(params, cfg, x, positions, enc_out)
    return L.apply_norm(params["final_norm"], x, cfg)


def last_token_logits(params, cfg: ModelConfig, batch: dict) -> Array:
    x = forward_hidden(params, cfg, batch)
    w = head_weight(params, cfg)
    return (x[:, -1, :] @ w.astype(x.dtype)).astype(jnp.float32)
