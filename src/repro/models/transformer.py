"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Pure-functional: ``init_params`` builds a pytree (layers stacked along a
leading L axis), ``forward``/``prefill``/``decode_step`` are jit-able, and
``param_specs`` returns the logical-axis pytree the sharding layer consumes.
Layers run under ``jax.lax.scan`` (bounded HLO at 512 devices) with optional
per-block remat.

An interleaved stack (``ModelConfig.layer_kinds``: Jamba) keeps one stack
per kind of layer, ``params["layers"][kind]`` in the layers' order, and runs
each run of consecutive layers of one kind as one scan over its stack, so
the published order is kept; its prefill and decode are not implemented.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from repro.distributed import context as ctx

from .config import ModelConfig
from .layers import (ParamDef, embed_table, embed_tokens, init_table,
                     lm_logits, mlp_forward, mlp_table, rms_norm, table_specs)


# --------------------------------------------------------------------------
# block structure
# --------------------------------------------------------------------------

@jax.custom_vjp
def _remat_barrier(x: jax.Array) -> jax.Array:
    """``optimization_barrier`` with an explicit VJP: identity-with-barrier
    on both passes.  Some jax versions ship no differentiation rule for the
    barrier primitive, which would make every ``scan_layers`` grad step
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
        t["norm_attn"] = {"scale": ParamDef((D,), ("embed",), init="ones")}
    if cfg.has_ssm:
        t["ssm"] = ssm_mod.ssm_table(cfg)
        if not cfg.has_attention:
            t["norm_ssm"] = {"scale": ParamDef((D,), ("embed",), init="ones")}
    if cfg.d_ff > 0:
        t["mlp"] = (moe_mod.moe_table(cfg) if cfg.is_moe
                    else mlp_table(D, cfg.d_ff))
        t["norm_mlp"] = {"scale": ParamDef((D,), ("embed",), init="ones")}
    return t


def init_block(cfg: ModelConfig, key: jax.Array, dtype) -> dict:
    tables = block_tables(cfg)
    keys = jax.random.split(key, len(tables))
    return {name: init_table(k, tbl, dtype)
            for (name, tbl), k in zip(sorted(tables.items()), keys)}


def block_specs(cfg: ModelConfig, stacked: bool) -> dict:
    lead = ("layers",) if stacked else ()
    return {name: {pname: lead + tuple(ax)
                   for pname, ax in table_specs(tbl).items()}
            for name, tbl in block_tables(cfg).items()}


# --------------------------------------------------------------------------
# block forward
# --------------------------------------------------------------------------

def _mix_forward(cfg: ModelConfig, p: dict, x: jax.Array, positions):
    """Sequence-mixing sublayer (attn / ssm / both).  Returns (out, caches)."""
    caches: dict[str, Any] = {}
    if cfg.has_attention and cfg.has_ssm:          # hybrid (hymba)
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        a_out, kv = attn.gqa_forward(cfg, p["attn"], h, positions)
        s_out, st = ssm_mod.ssm_forward(cfg, p["ssm"], h)
        caches["kv"], caches["ssm"] = kv, st
        return 0.5 * (a_out + s_out), caches
    if cfg.has_attention:
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        if cfg.attention == "mla":
            out, kv = attn.mla_forward(cfg, p["attn"], h, positions)
        else:
            out, kv = attn.gqa_forward(cfg, p["attn"], h, positions)
        caches["kv"] = kv
        return out, caches
    h = rms_norm(x, p["norm_ssm"]["scale"], cfg.norm_eps)
    out, st = ssm_mod.ssm_forward(cfg, p["ssm"], h)
    caches["ssm"] = st
    return out, caches


def _mix_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                index) -> tuple[jax.Array, dict]:
    new_cache: dict[str, Any] = {}
    if cfg.has_attention and cfg.has_ssm:
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        a_out, kv = attn.gqa_decode(cfg, p["attn"], h, cache["kv"], index)
        s_out, st = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache["ssm"])
        new_cache["kv"], new_cache["ssm"] = kv, st
        return 0.5 * (a_out + s_out), new_cache
    if cfg.has_attention:
        h = rms_norm(x, p["norm_attn"]["scale"], cfg.norm_eps)
        if cfg.attention == "mla":
            out, kv = attn.mla_decode(cfg, p["attn"], h, cache["kv"], index)
        else:
            out, kv = attn.gqa_decode(cfg, p["attn"], h, cache["kv"], index)
        new_cache["kv"] = kv
        return out, new_cache
    h = rms_norm(x, p["norm_ssm"]["scale"], cfg.norm_eps)
    out, st = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache["ssm"])
    new_cache["ssm"] = st
    return out, new_cache


def _ffn_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.d_ff == 0:
        return jnp.zeros_like(x)
    h = rms_norm(x, p["norm_mlp"]["scale"], cfg.norm_eps)
    if cfg.is_moe:
        return moe_mod.moe_forward(cfg, p["mlp"], h)
    return mlp_forward(p["mlp"], h, cfg.act)


def block_forward(cfg: ModelConfig, p: dict, x: jax.Array, positions,
                  ) -> tuple[jax.Array, dict]:
    # keep the scan-carried activation batch-sharded: without this, GSPMD
    # sometimes replicates while-loop carries and the whole layer stack
    # (and everything downstream) runs with batch unsharded.  With
    # seq_sharded_residual the carry (and thus the remat-saved stack) is
    # additionally sharded over `model` on the seq dim; the mix/ffn
    # sublayers gather it back (Megatron sequence parallelism).
    if cfg.seq_sharded_residual:
        x = ctx.constrain(x, ctx.dp(), "model", None)
    else:
        x = ctx.constrain(x, ctx.dp(), None, None)
    # pin the remat-saved layer input to bf16: without the barrier XLA
    # hoists the norm's f32 upcast into the saved stack (3x the memory)
    x = _remat_barrier(x)
    # named scopes reach every compiled op's ``op_name`` metadata, which
    # is how a device trace's ops are put on layers; a hybrid's mixer
    # counts as attention
    with jax.named_scope("attention" if cfg.has_attention else "ssm"):
        mix, caches = _mix_forward(cfg, p, x, positions)
    x = x + mix
    if cfg.d_ff > 0:
        with jax.named_scope("mlp"):
            x = x + _ffn_forward(cfg, p, x)
    return x, caches


def block_decode(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                 index) -> tuple[jax.Array, dict]:
    mix, new_cache = _mix_decode(cfg, p, x, cache, index)
    x = x + mix
    if cfg.d_ff > 0:
        x = x + _ffn_forward(cfg, p, x)
    return x, new_cache


def _head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    """Final norm and the vocabulary projection, under the ``head``
    scope."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["embed"]["final_norm"], cfg.norm_eps)
        return lm_logits(params["embed"], x, cfg.tie_embeddings)


# --------------------------------------------------------------------------
# model init / specs
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
    elif cfg.scan_layers:
        params["layers"] = jax.vmap(
            lambda k: init_block(cfg, k, dtype))(layer_keys)
    else:
        params["layers"] = [init_block(cfg, k, dtype) for k in layer_keys]
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


def param_specs(cfg: ModelConfig) -> dict:
    if cfg.layer_kinds:
        layers = {kind: block_specs(cfg.kind_config(kind), True)
                  for kind in _kind_layers(cfg)}
    else:
        blocks = block_specs(cfg, cfg.scan_layers)
        layers = (blocks if cfg.scan_layers
                  else [blocks for _ in range(cfg.num_layers)])
    return {
        "embed": table_specs(
            embed_table(cfg.padded_vocab, cfg.d_model, cfg.tie_embeddings)),
        "layers": layers,
    }


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _default_positions(cfg: ModelConfig, B: int, S: int,
                       offset: int = 0) -> jax.Array:
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
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
    x = ctx.constrain(x, ctx.dp(), None, None)
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
            return block(lp, h, positions)[0], None

        x, _ = jax.lax.scan(body, x, jnp.arange(start, stop))
    return x


def _run_layers(cfg: ModelConfig, params, x, positions,
                collect_caches: bool = False):
    if cfg.layer_kinds:
        if collect_caches:
            raise NotImplementedError(
                f"{cfg.name}: prefill of an interleaved stack")
        return _run_interleaved(cfg, params, x, positions), None
    block = _remat(cfg, functools.partial(block_forward, cfg))
    if cfg.scan_layers:
        def body(h, lp):
            h2, caches = block(lp, h, positions)
            return h2, (caches if collect_caches else None)
        x, caches = jax.lax.scan(body, x, params["layers"])
    else:
        caches = []
        for lp in params["layers"]:
            x, c = block(lp, x, positions)
            caches.append(c)
    return x, caches


def forward(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """Full-sequence forward -> logits (B, S, V)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, _ = _run_layers(cfg, params, x, positions)
    x = ctx.constrain(x, ctx.dp(), None, None)
    return _head(cfg, params, x)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    logits = forward(cfg, params, batch)
    return cross_entropy(logits, batch["labels"], batch.get("loss_mask"),
                         real_vocab=cfg.vocab_size)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask=None, real_vocab: int = 0) -> jax.Array:
    """Vocab-shard-friendly cross entropy: the label log-prob is picked with
    a one-hot einsum (NOT take_along_axis — gathering along a `model`-sharded
    vocab axis makes GSPMD replicate the full f32 logits; the einsum lowers
    to a partial reduction + tiny all-reduce instead)."""
    from repro.distributed import context as ctx
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logits = ctx.constrain(logits.astype(jnp.float32),
                           ctx.dp(), None, "model")
    if real_vocab and real_vocab < logits.shape[-1]:
        # vocab is padded to shard evenly; padding columns must not leak
        # probability mass into the partition function
        pad_mask = jnp.arange(logits.shape[-1]) < real_vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    shifted = logits - m
    logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    ll = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = (logz - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


# --------------------------------------------------------------------------
# serving: prefill + decode
# --------------------------------------------------------------------------

class DecodeState(NamedTuple):
    cache: Any          # per-layer cache pytree, leaves stacked over L
    index: jax.Array    # scalar int32: #tokens written
    last_logits: jax.Array


def prefill(cfg: ModelConfig, params: dict, batch: dict,
            s_max: int) -> DecodeState:
    """Run the prompt, building caches padded out to ``s_max``."""
    x, positions = _embed_inputs(cfg, params, batch)
    B, S = x.shape[:2]
    x, caches = _run_layers(cfg, params, x, positions, collect_caches=True)
    x = rms_norm(x, params["embed"]["final_norm"], cfg.norm_eps)
    logits = lm_logits(params["embed"], x[:, -1:], cfg.tie_embeddings)

    # pad prefill KV out to s_max; works for stacked (L, B, S, ...) and
    # per-layer (B, S, ...) caches via negative seq axis.
    def pad_kv(c: attn.KVCache) -> attn.KVCache:
        cap = s_max
        if cfg.sliding_window:
            cap = min(cap, cfg.sliding_window)   # decode ring buffer size
        def pad(a):   # (..., S, KV, hd) -> (..., cap, KV, hd)
            ax = a.ndim - 3
            Sp = a.shape[ax]
            if Sp >= cap:
                # keep the last `cap` positions and rotate them into ring
                # layout: position p lives at slot p % cap
                sl = [slice(None)] * a.ndim
                sl[ax] = slice(Sp - cap, None)
                return jnp.roll(a[tuple(sl)], Sp % cap, axis=ax)
            padw = [(0, 0)] * a.ndim
            padw[ax] = (0, cap - Sp)
            return jnp.pad(a, padw)
        return attn.KVCache(pad(c.k), pad(c.v))

    def pad_mla(c: attn.MLACache) -> attn.MLACache:
        def pad(a):   # (..., S, R)
            padw = [(0, 0)] * a.ndim
            padw[a.ndim - 2] = (0, s_max - a.shape[a.ndim - 2])
            return jnp.pad(a, padw)
        return attn.MLACache(pad(c.latent), pad(c.k_rope))

    def pad_one(caches_dict):
        out = {}
        if "kv" in caches_dict:
            out["kv"] = (pad_mla(caches_dict["kv"])
                         if cfg.attention == "mla"
                         else pad_kv(caches_dict["kv"]))
        if "ssm" in caches_dict:
            out["ssm"] = caches_dict["ssm"]
        return out

    if isinstance(caches, dict):
        new_caches = pad_one(caches)
    else:                               # unrolled: list of per-layer dicts
        new_caches = [pad_one(c) for c in caches]
    return DecodeState(new_caches, jnp.int32(S), logits)


def init_decode_state(cfg: ModelConfig, batch: int, s_max: int,
                      index: int = 0) -> DecodeState:
    """Empty caches at full length — the decode-only benchmark entrypoint
    (the decode_32k / long_500k cells lower THIS, with index = seq_len)."""
    if cfg.layer_kinds:
        raise NotImplementedError(
            f"{cfg.name}: decode of an interleaved stack")
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.num_layers

    def layer_cache() -> dict:
        c: dict[str, Any] = {}
        if cfg.has_attention:
            c["kv"] = (attn.mla_empty_cache(cfg, batch, s_max, dtype)
                       if cfg.attention == "mla"
                       else attn.gqa_empty_cache(cfg, batch, s_max, dtype))
        if cfg.has_ssm:
            c["ssm"] = ssm_mod.ssm_empty_cache(cfg, batch, dtype)
        return c

    if cfg.scan_layers:
        one = layer_cache()
        cache: Any = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), one)
    else:
        cache = [layer_cache() for _ in range(L)]
    logits = jnp.zeros((batch, 1, cfg.padded_vocab), jnp.float32)
    return DecodeState(cache, jnp.int32(index), logits)


def decode_step(cfg: ModelConfig, params: dict, state: DecodeState,
                tokens: jax.Array) -> DecodeState:
    """One token for every sequence. tokens: (B, 1) int32."""
    if cfg.layer_kinds:
        raise NotImplementedError(
            f"{cfg.name}: decode of an interleaved stack")
    dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(params["embed"], tokens, dtype)
    index = state.index

    def body(h, lp_cache):
        lp, cache = lp_cache
        h2, new_cache = block_decode(cfg, lp, h, cache, index)
        return h2, new_cache

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (params["layers"], state.cache))
    else:
        new_list = []
        for lp, c in zip(params["layers"], state.cache):
            x, nc = block_decode(cfg, lp, x, c, index)
            new_list.append(nc)
        new_caches = new_list
    logits = _head(cfg, params, x)
    return DecodeState(new_caches, index + 1, logits)
