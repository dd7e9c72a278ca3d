"""Mixture-of-Experts channel mixer with expert parallelism.

Design (DESIGN.md §6): activations are sharded over the data axes and
*replicated* over the ``model`` axis; experts are sharded over ``model``.
Every device therefore already holds the tokens of its data shard and the
weights of its expert shard — dispatch is purely local (gather into an
(E_local, capacity, d) buffer), expert FFNs run as one batched einsum, and
a single ``psum`` over ``model`` merges the per-expert partial outputs.
No all-to-all, no cross-shard scatter: the paper's host-merge structure
(I5) applied to MoE.

Capacity-based token dropping (Switch-style) keeps shapes static; dropped
tokens fall back to the residual stream.  A Switch load-balance auxiliary
loss is returned for the trainer.

Two code paths share the body: ``shard_map`` when sharding rules are
active, plain single-device execution otherwise (smoke tests).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import common as cm
from repro.distributed.sharding import current_rules


def init_moe(cfg: cm.ModelConfig, key: jax.Array) -> dict:
    mc = cfg.moe
    d, f, E = cfg.d_model, mc.d_ff, mc.n_experts
    dt = cfg.compute_dtype
    ks = cm.split_keys(key, 4)
    return {
        "router": cm.dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": cm.dense_init(ks[1], (E, d, f), dt),
        "w_up": cm.dense_init(ks[2], (E, d, f), dt),
        "w_down": cm.dense_init(ks[3], (E, f, d), dt, fan_in=f),
    }


def _moe_body(cfg: cm.ModelConfig, p: dict, x: jax.Array,
              e_offset, n_local: int) -> Tuple[jax.Array, jax.Array]:
    """Per-device MoE: x (T, d) local tokens, p holds n_local experts.

    Returns (partial_y (T, d), aux_loss scalar)."""
    mc = cfg.moe
    T, d = x.shape
    E, k = mc.n_experts, mc.top_k
    C = max(1, int(T * k * mc.capacity_factor / E))

    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    topw, topi = jax.lax.top_k(probs, k)                     # (T, k)
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # Switch aux loss: E * Σ_e (fraction routed to e) * (mean prob of e)
    sel = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    aux = E * jnp.sum(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))

    # position of each (token, choice) within its expert's capacity
    onehot = jax.nn.one_hot(topi.reshape(-1), E, dtype=jnp.int32)  # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1        # (T*k,)
    pos = pos.reshape(T, k)

    tok_ids = jnp.arange(T, dtype=jnp.int32)
    buf = jnp.zeros((n_local, C, d), x.dtype)
    masks, slots = [], []
    for j in range(k):                    # k is 2..8: unrolled dispatch
        e = topi[:, j]
        local = (e >= e_offset) & (e < e_offset + n_local)
        ok = local & (pos[:, j] < C)
        le = jnp.clip(e - e_offset, 0, n_local - 1)
        ps = jnp.clip(pos[:, j], 0, C - 1)
        contrib = jnp.where(ok[:, None], x, 0)
        buf = buf.at[le, ps].add(contrib, mode="drop")
        masks.append(ok)
        slots.append((le, ps))

    # batched expert FFN (SwiGLU), MXU-shaped: (E_loc, C, d) x (E_loc, d, f)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])         # (E_loc, C, d)

    y = jnp.zeros((T, d), x.dtype)
    for j in range(k):
        le, ps = slots[j]
        got = out[le, ps]                                     # (T, d)
        w = jnp.where(masks[j], topw[:, j], 0.0).astype(x.dtype)
        y = y + got * w[:, None]
    return y, aux


def moe_ffn(cfg: cm.ModelConfig, p: dict, x: jax.Array
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    mc = cfg.moe
    rules = current_rules()

    if rules is None or rules.table.get("experts") is None:
        y, aux = _moe_body(cfg, p, x.reshape(B * S, d), 0, mc.n_experts)
        return y.reshape(B, S, d), aux

    mesh = rules.mesh
    ep_axis = rules.table["experts"]
    msize = mesh.shape[ep_axis]
    if mc.n_experts % msize:
        y, aux = _moe_body(cfg, p, x.reshape(B * S, d), 0, mc.n_experts)
        return y.reshape(B, S, d), aux
    n_local = mc.n_experts // msize
    dp = rules.table.get("batch")
    x_spec = P(dp, None, None)
    p_specs = {
        "router": P(),
        "w_gate": P(ep_axis, None, None),
        "w_up": P(ep_axis, None, None),
        "w_down": P(ep_axis, None, None),
    }

    def body(p, x):
        Bl, Sl, _ = x.shape
        m = jax.lax.axis_index(ep_axis)
        y, aux = _moe_body(cfg, p, x.reshape(Bl * Sl, d),
                           m * n_local, n_local)
        # the paper's host-merge: one reduction combines expert partials
        y = jax.lax.psum(y, ep_axis)
        aux = jax.lax.psum(aux, ep_axis) / msize
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(Bl, Sl, d), aux

    y, aux = shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=(x_spec, P()), check_vma=False)(p, x)
    return y, aux
