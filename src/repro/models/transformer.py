"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Pure-functional: ``init_params`` builds a pytree (layers stacked along a
leading L axis) and ``forward`` is the jit-able full-sequence forward.
Layers run under ``jax.lax.scan`` (bounded HLO whatever the depth) with
optional per-block remat.

An interleaved stack (``ModelConfig.layer_kinds``: Jamba) keeps one stack
per kind of layer, ``params["layers"][kind]`` in the layers' order, and runs
each run of consecutive layers of one kind as one scan over its stack, so
the published order is kept.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod

from .config import ModelConfig
from .layers import (ParamDef, embed_table, embed_tokens, init_table,
                     lm_logits, mlp_forward, mlp_table, rms_norm)


# --------------------------------------------------------------------------
# block structure
# --------------------------------------------------------------------------

@jax.custom_vjp
def _remat_barrier(x: jax.Array) -> jax.Array:
    """``optimization_barrier`` with an explicit VJP: identity-with-barrier
    on both passes.  Some jax versions ship no differentiation rule for the
    barrier primitive, which would make every layer-stack grad step
    raise ``NotImplementedError`` — the custom rule keeps the memory-pinning
    barrier in the forward *and* backward HLO without relying on one."""
    return jax.lax.optimization_barrier(x)


def _remat_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _remat_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_remat_barrier.defvjp(_remat_barrier_fwd, _remat_barrier_bwd)



def block_tables(cfg: ModelConfig) -> dict[str, dict[str, ParamDef]]:
    D = cfg.d_model
    t: dict[str, dict[str, ParamDef]] = {}
    if cfg.has_attention:
        t["attn"] = (attn.mla_table(cfg) if cfg.attention == "mla"
                     else attn.gqa_table(cfg))
        t["norm_attn"] = {"scale": ParamDef((D,), init="ones")}
    if cfg.has_ssm:
        t["ssm"] = ssm_mod.ssm_table(cfg)
        if not cfg.has_attention:
            t["norm_ssm"] = {"scale": ParamDef((D,), init="ones")}
    if cfg.d_ff > 0:
        t["mlp"] = (moe_mod.moe_table(cfg) if cfg.is_moe
                    else mlp_table(D, cfg.d_ff))
        t["norm_mlp"] = {"scale": ParamDef((D,), init="ones")}
    return t


def init_block(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    tables = block_tables(cfg)
    keys = jax.random.split(key, len(tables))
    return {name: init_table(k, tbl, dtype)
            for (name, tbl), k in zip(sorted(tables.items()), keys)}


# --------------------------------------------------------------------------
# block forward
# --------------------------------------------------------------------------

def _mix_forward(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    """Sequence-mixing sublayer (attn / ssm / both)."""
    if cfg.has_attention and cfg.has_ssm:          # hybrid (hymba)
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        return 0.5 * (attn.gqa_forward(cfg, p["attn"], h, positions)
                      + ssm_mod.ssm_forward(cfg, p["ssm"], h))
    if cfg.has_attention:
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        if cfg.attention == "mla":
            return attn.mla_forward(cfg, p["attn"], h, positions)
        return attn.gqa_forward(cfg, p["attn"], h, positions)
    h = rms_norm(x, p["norm_ssm"]["scale"], cfg.norm_eps)
    return ssm_mod.ssm_forward(cfg, p["ssm"], h)


def _ffn_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.d_ff == 0:
        return jnp.zeros_like(x)
    h = rms_norm(x, p["norm_mlp"]["scale"], cfg.norm_eps)
    if cfg.is_moe:
        return moe_mod.moe_forward(cfg, p["mlp"], h)
    return mlp_forward(p["mlp"], h, cfg.act)


def block_forward(cfg: ModelConfig, p: dict, x: jax.Array,
                  positions) -> jax.Array:
    # pin the remat-saved layer input to bf16: without the barrier XLA
    # hoists the norm's f32 upcast into the saved stack (3x the memory)
    x = _remat_barrier(x)
    # named scopes reach every compiled op's ``op_name`` metadata, which
    # is how a device trace's ops are put on layers; a hybrid's mixer
    # counts as attention
    with jax.named_scope("attention" if cfg.has_attention else "ssm"):
        mix = _mix_forward(cfg, p, x, positions)
    x = x + mix
    if cfg.d_ff > 0:
        with jax.named_scope("mlp"):
            x = x + _ffn_forward(cfg, p, x)
    return x


def _head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Final norm and the vocabulary projection, under the ``head``
    scope."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["embed"]["final_norm"], cfg.norm_eps)
        return lm_logits(params["embed"], x, cfg.tie_embeddings)


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    k_emb, k_layers = jax.random.split(key)
    params = {"embed": init_table(
        k_emb, embed_table(cfg.padded_vocab, cfg.d_model,
                           cfg.tie_embeddings), dtype)}
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.layer_kinds:
        # layer i draws from layer_keys[i] by its kind's tables; each kind's
        # layers are stacked in order
        params["layers"] = {
            kind: jax.vmap(lambda k, c=cfg.kind_config(kind): init_block(
                c, k, dtype))(layer_keys[jnp.array(idx)])
            for kind, idx in _kind_layers(cfg).items()}
    else:
        params["layers"] = jax.vmap(
            lambda k: init_block(cfg, k, dtype))(layer_keys)
    return params


def _kind_layers(cfg: ModelConfig) -> dict[str, list[int]]:
    """{kind: indices of its layers, in order} of an interleaved stack."""
    out: dict[str, list[int]] = {}
    for i, kind in enumerate(cfg.layer_kinds):
        out.setdefault(kind, []).append(i)
    return out


def _kind_runs(cfg: ModelConfig) -> list[tuple[str, int, int]]:
    """(kind, start, stop) of each run of consecutive layers of one kind,
    in order; start and stop index the kind's stack."""
    runs, seen = [], {}
    for kind, run in itertools.groupby(cfg.layer_kinds):
        start = seen.get(kind, 0)
        seen[kind] = start + len(list(run))
        runs.append((kind, start, seen[kind]))
    return runs


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _default_positions(cfg: ModelConfig, B: int, S: int) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.rope_type == "mrope":
        return jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


def _embed_inputs(cfg: ModelConfig, params, batch: dict) -> tuple:
    dtype = jnp.dtype(cfg.dtype)
    if "embeds" in batch:            # vlm/audio stub frontends feed embeddings
        x = batch["embeds"].astype(dtype)
    else:
        x = embed_tokens(params["embed"], batch["tokens"], dtype)
    B, S = x.shape[:2]
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    return x, positions


def _remat(cfg: ModelConfig, block):
    if cfg.remat == "none":
        return block
    return jax.checkpoint(
        block, policy=jax.checkpoint_policies.nothing_saveable
        if cfg.remat == "full" else
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _run_interleaved(cfg: ModelConfig, params, x, positions):
    """Each run of consecutive layers of one kind as one scan over the
    indices of its kind's stack."""
    for kind, start, stop in _kind_runs(cfg):
        block = _remat(cfg, functools.partial(block_forward,
                                              cfg.kind_config(kind)))
        stack = params["layers"][kind]

        def body(h, i, block=block, stack=stack):
            lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False), stack)
            return block(lp, h, positions), None

        x, _ = jax.lax.scan(body, x, jnp.arange(start, stop))
    return x


def _run_layers(cfg: ModelConfig, params, x, positions):
    if cfg.layer_kinds:
        return _run_interleaved(cfg, params, x, positions)
    block = _remat(cfg, functools.partial(block_forward, cfg))

    def body(h, lp):
        return block(lp, h, positions), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def forward(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x = _run_layers(cfg, params, x, positions)
    return _head(cfg, params, x)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    logits = forward(cfg, params, batch)
    return cross_entropy(logits, batch["labels"], batch.get("loss_mask"),
                         real_vocab=cfg.vocab_size)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask=None, real_vocab: int = 0) -> jax.Array:
    """Mean cross entropy of ``labels`` under ``logits`` over ``mask``;
    the label log-prob is picked with a one-hot einsum."""
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logits = logits.astype(jnp.float32)
    if real_vocab and real_vocab < logits.shape[-1]:
        # the vocab is padded (ModelConfig.padded_vocab); padding columns
        # must not leak probability mass into the partition function
        pad_mask = jnp.arange(logits.shape[-1]) < real_vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    ll = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = (logz - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
