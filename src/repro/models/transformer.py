"""Decoder-only LM stack: periodic-pattern scan, remat, train loss,
prefill and single-token decode.

The layer pattern (ATTN / LOCAL_ATTN / MAMBA2 / RGLRU) is split into the
smallest repeating unit; the stack ``lax.scan``s over unit repetitions
(HLO size independent of depth — required for 94L x 512-device dry-runs)
and unrolls the non-periodic tail (e.g. recurrentgemma's 26 = 3x8 + 2).

Caches mirror the parameter layout: a stacked pytree per scanned group +
a list for the tail, so decode is also a single scan.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import attention as att
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import rglru as rglru_mod
from repro.distributed.sharding import shard_hint


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------

def init_layer(cfg: cm.ModelConfig, kind: str, key: jax.Array) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind == cm.MAMBA2:
        return {"norm1": cm.init_norm(cfg),
                "mixer": ssm_mod.init_mamba2(cfg, k1)}
    p: Dict[str, Any] = {"norm1": cm.init_norm(cfg),
                         "norm2": cm.init_norm(cfg)}
    if kind in (cm.ATTN, cm.LOCAL_ATTN):
        p["mixer"] = att.init_attn(cfg, k1)
    elif kind == cm.RGLRU:
        p["mixer"] = rglru_mod.init_rglru(cfg, k1)
    else:
        raise ValueError(kind)
    if cfg.moe is not None:
        p["moe"] = moe_mod.init_moe(cfg, k2)
    else:
        p["mlp"] = mlp_mod.init_mlp(cfg, k3)
    return p


def _channel_mix(cfg, p, x):
    """Second residual branch. Returns (delta, aux)."""
    h = cm.apply_norm(cfg, p["norm2"], x)
    if cfg.moe is not None:
        return moe_mod.moe_ffn(cfg, p["moe"], h)
    return mlp_mod.mlp(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)


def layer_forward(cfg: cm.ModelConfig, kind: str, p: dict, x: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence layer. Returns (x, aux_loss)."""
    h = cm.apply_norm(cfg, p["norm1"], x)
    if kind == cm.ATTN:
        mix = att.attn_full(cfg, p["mixer"], h, positions, causal=True)
    elif kind == cm.LOCAL_ATTN:
        mix = att.attn_full(cfg, p["mixer"], h, positions, causal=True,
                            window=cfg.window)
    elif kind == cm.MAMBA2:
        return x + ssm_mod.mamba2_forward(cfg, p["mixer"], h), \
            jnp.zeros((), jnp.float32)
    elif kind == cm.RGLRU:
        mix = rglru_mod.rglru_forward(cfg, p["mixer"], h)
    else:
        raise ValueError(kind)
    x = x + mix
    delta, aux = _channel_mix(cfg, p, x)
    return x + delta, aux


def init_layer_cache(cfg: cm.ModelConfig, kind: str, batch: int,
                     max_len: int) -> dict:
    if kind == cm.ATTN:
        return att.init_cache(cfg, batch, max_len)
    if kind == cm.LOCAL_ATTN:
        return att.init_cache(cfg, batch, max_len, window=cfg.window)
    if kind == cm.MAMBA2:
        return ssm_mod.init_mamba2_cache(cfg, batch)
    if kind == cm.RGLRU:
        return rglru_mod.init_rglru_cache(cfg, batch)
    raise ValueError(kind)


def layer_decode(cfg: cm.ModelConfig, kind: str, p: dict, x: jax.Array,
                 cache: dict, pos: jax.Array) -> Tuple[jax.Array, dict]:
    h = cm.apply_norm(cfg, p["norm1"], x)
    if kind == cm.ATTN:
        mix, cache = att.attn_decode(cfg, p["mixer"], h, cache, pos)
    elif kind == cm.LOCAL_ATTN:
        mix, cache = att.attn_decode(cfg, p["mixer"], h, cache, pos,
                                     window=cfg.window)
    elif kind == cm.MAMBA2:
        mix, cache = ssm_mod.mamba2_decode(cfg, p["mixer"], h, cache)
        return x + mix, cache
    elif kind == cm.RGLRU:
        mix, cache = rglru_mod.rglru_decode(cfg, p["mixer"], h, cache)
    else:
        raise ValueError(kind)
    x = x + mix
    delta, _ = _channel_mix(cfg, p, x)
    return x + delta, cache


# ---------------------------------------------------------------------------
# stack (scan over periodic groups + unrolled tail)
# ---------------------------------------------------------------------------

def init_stack(cfg: cm.ModelConfig, key: jax.Array) -> dict:
    unit, reps, tail = cfg.scan_groups()
    keys = jax.random.split(key, reps + len(tail) + 1)

    def init_group(k):
        ks = jax.random.split(k, len(unit))
        return tuple(init_layer(cfg, kind, ki)
                     for kind, ki in zip(unit, ks))

    groups = [init_group(keys[i]) for i in range(reps)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *groups) if reps > 1 \
        else jax.tree.map(lambda x: x[None], init_group(keys[0]))
    tail_p = [init_layer(cfg, kind, keys[reps + i])
              for i, kind in enumerate(tail)]
    return {"scan": stacked, "tail": tail_p}


def _group_forward(cfg, unit, gp, x, positions):
    aux = jnp.zeros((), jnp.float32)
    for kind, p in zip(unit, gp):
        x, a = layer_forward(cfg, kind, p, x, positions)
        # sequence-parallel residual stream: the remat-saved carry is
        # seq-sharded over `model` (16x activation-memory reduction)
        x = shard_hint(x, "batch", "seq_act", None)
        aux = aux + a
    return x, aux


def stack_forward(cfg: cm.ModelConfig, params: dict, x: jax.Array,
                  positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    unit, reps, tail = cfg.scan_groups()

    body = functools.partial(_group_forward, cfg, unit)
    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, gp):
        y, aux = body(gp, carry, positions)
        return y, aux

    x, auxs = jax.lax.scan(scan_body, x, params["scan"])
    aux = jnp.sum(auxs)
    for kind, p in zip(tail, params["tail"]):
        x, a = layer_forward(cfg, kind, p, x, positions)
        aux = aux + a
    return x, aux


def init_stack_cache(cfg: cm.ModelConfig, batch: int, max_len: int) -> dict:
    unit, reps, tail = cfg.scan_groups()

    def group_cache():
        return tuple(init_layer_cache(cfg, kind, batch, max_len)
                     for kind in unit)

    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape),
        group_cache())
    tail_c = [init_layer_cache(cfg, kind, batch, max_len) for kind in tail]
    return {"scan": stacked, "tail": tail_c}


def stack_decode(cfg: cm.ModelConfig, params: dict, caches: dict,
                 x: jax.Array, pos: jax.Array) -> Tuple[jax.Array, dict]:
    unit, reps, tail = cfg.scan_groups()

    def scan_body(carry, pc):
        gp, gc = pc
        y = carry
        new_cs = []
        for kind, p, c in zip(unit, gp, gc):
            y, nc = layer_decode(cfg, kind, p, y, c, pos)
            new_cs.append(nc)
        return y, tuple(new_cs)

    x, new_scan = jax.lax.scan(scan_body, x,
                               (params["scan"], caches["scan"]))
    new_tail = []
    for kind, p, c in zip(tail, params["tail"], caches["tail"]):
        x, nc = layer_decode(cfg, kind, p, x, c, pos)
        new_tail.append(nc)
    return x, {"scan": new_scan, "tail": new_tail}


# ---------------------------------------------------------------------------
# LM: embeddings + stack + head, loss / prefill / decode
# ---------------------------------------------------------------------------

def padded_vocab(cfg: cm.ModelConfig) -> int:
    return -(-cfg.vocab_size // 128) * 128


def init_lm(cfg: cm.ModelConfig, key: jax.Array) -> dict:
    k_emb, k_stack, k_head = jax.random.split(key, 3)
    V = padded_vocab(cfg)
    params = {
        "embed": cm.dense_init(k_emb, (V, cfg.d_model), cfg.compute_dtype,
                               fan_in=cfg.d_model),
        "stack": init_stack(cfg, k_stack),
        "final_norm": cm.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = cm.dense_init(
            k_head, (cfg.d_model, V), cfg.compute_dtype)
    return params


def _embed(cfg, params, tokens):
    x = _sharded_lookup(params["embed"], tokens)
    if cfg.emb_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return shard_hint(x, "batch", "seq_act", "embed_act")


def _sharded_lookup(table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Vocab-sharded embedding lookup.

    GSPMD partitions the gather by replicating the table, and — much
    worse — the backward *scatter* materializes a full replicated f32
    (V, d) gradient that drags the whole Adam update replicated
    (qwen1.5-110b: 6 x 4.6GB per device).  The shard_map form keeps both
    directions local: each model rank gathers/masks its vocab slice and
    one psum over `model` combines; the transpose is a local scatter
    into the rank's (V/16, d) slice."""
    from repro.distributed.sharding import current_rules
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rules = current_rules()
    vocab_ax = rules.table.get("vocab") if rules else None
    if rules is None or vocab_ax is None or \
            table.shape[0] % rules.mesh.shape[vocab_ax]:
        return jnp.take(table, tokens, axis=0)
    dp = rules.table.get("batch")
    if dp:
        import numpy as _np
        dp_size = int(_np.prod([rules.mesh.shape[a] for a in dp]))
        if tokens.shape[0] % dp_size:
            dp = None          # batch=1 decode: replicate tokens

    def body(tab, tok):
        m = jax.lax.axis_index(vocab_ax)
        v_loc = tab.shape[0]
        local = tok - m * v_loc
        ok = (local >= 0) & (local < v_loc)
        x = jnp.take(tab, jnp.clip(local, 0, v_loc - 1), axis=0)
        x = jnp.where(ok[..., None], x, 0)
        return jax.lax.psum(x, vocab_ax)

    return shard_map(
        body, mesh=rules.mesh,
        in_specs=(P(vocab_ax, None), P(dp, None)),
        out_specs=P(dp, None, None), check_vma=False)(table, tokens)


def _head(cfg, params, x):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"])
    logits = shard_hint(logits, "batch", "seq", "vocab")
    V, Vp = cfg.vocab_size, padded_vocab(cfg)
    if Vp != V:  # mask pad columns out of the softmax
        pad_bias = jnp.where(jnp.arange(Vp) < V, 0.0, -1e9)
        logits = logits + pad_bias.astype(logits.dtype)
    return logits


def lm_forward(cfg: cm.ModelConfig, params: dict, tokens: jax.Array,
               prefix_embeds: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S_tok) [+ prefix (B, P, d) frontend-stub embeddings]
    -> (logits (B, S, Vp), aux)."""
    x = _embed(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, aux = stack_forward(cfg, params["stack"], x, positions)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    return _head(cfg, params, x), aux


def lm_loss(cfg: cm.ModelConfig, params: dict, batch: dict,
            aux_weight: float = 0.01, ce_chunk: int = 512
            ) -> Tuple[jax.Array, dict]:
    """batch: {"tokens": (B,S)} [+ "prefix_embeds"] — next-token CE.

    The CE is computed in seq chunks over the *hidden* states so the
    (B, S, V) f32 logits never materialize (qwen1.5-110b: −9GB/device;
    §Perf iteration).  Each chunk re-runs the head matmul (same FLOPs)
    under remat."""
    tokens = batch["tokens"]
    logits, aux = lm_forward(cfg, params, tokens,
                             batch.get("prefix_embeds"))
    # align: predictions for token positions only (prefix has no labels)
    n_prefix = logits.shape[1] - tokens.shape[1]
    logits = logits[:, n_prefix:, :]
    ce = cross_entropy(logits, tokens)
    loss = ce + aux_weight * aux
    # NOTE (§Perf, refuted): computing CE in seq chunks over hidden
    # states (never materializing (B,S,V) f32) was tried and REVERTED —
    # the chunk reshape breaks the sequence-parallel sharding and the
    # resulting gathers cost more memory than the chunking saved
    # (qwen2-0.5b 8.3 -> 12.5 GB/dev, qwen1.5-110b flat).
    return loss, {"ce": ce, "aux": aux}


def cross_entropy(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token CE, vocab-sharding friendly: the gold logit is read via
    a one-hot contraction (local partial + psum under GSPMD) instead of
    take_along_axis, which would all-gather the full logits across the
    ``model`` axis (40+GB for 150k vocabs)."""
    lg = logits[:, :-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    onehot = jax.nn.one_hot(tg, lg.shape[-1], dtype=lg.dtype)
    gold = jnp.einsum("bsv,bsv->bs", lg, onehot)
    return jnp.mean(lse - gold)


def lm_init_cache(cfg: cm.ModelConfig, batch: int, max_len: int) -> dict:
    return init_stack_cache(cfg, batch, max_len)


def lm_decode_step(cfg: cm.ModelConfig, params: dict, cache: dict,
                   token: jax.Array, pos: jax.Array
                   ) -> Tuple[jax.Array, dict]:
    """token (B, 1) + absolute position scalar -> (logits (B,1,V), cache)."""
    x = _embed(cfg, params, token)
    x, cache = stack_decode(cfg, params["stack"], cache, x, pos)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    return _head(cfg, params, x), cache


def lm_prefill(cfg: cm.ModelConfig, params: dict, tokens: jax.Array,
               prefix_embeds: Optional[jax.Array] = None
               ) -> jax.Array:
    """Prefill pass: full-sequence forward returning last-position logits.

    (Cache materialization for chained decode is serviced by
    ``lm_decode_step`` re-running positions; the dry-run prefill cell
    measures the full-context forward, which dominates.)"""
    logits, _ = lm_forward(cfg, params, tokens, prefix_embeds)
    return logits[:, -1:, :]
