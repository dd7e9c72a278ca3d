"""Logistic regression cells: the estimator under test, its data, the
work of one local step, and a plain reference of the same algorithm.

The algorithm (the program's ``LogReg(precision="int8", sigmoid="lut")``
as the paper describes it): the rows are quantized once to int8 with a
symmetric scale per feature and split over ``n_vdpus`` vDPUs of
``rows_per_vdpu`` rows.  Each step every vDPU quantizes the weights
(times the feature scales) to 16 bits with one scale, computes its
logits with integer products, looks the sigmoid up in a 1,024-entry
table on [-8, 8] (nearest entry), quantizes its residuals to 16 bits
with a scale of its own, and forms its partial gradient with integer
products.  The partials are summed over all vDPUs and the weights take
a gradient step of ``lr`` on their mean.  With ``batch_size`` each step
uses a sampled batch of each vDPU's rows, scaled up to the partition.

The reference below follows that description with ``jax.numpy`` and
``numpy`` alone: it imports nothing of the program and takes nothing the
program made (scales, table and sample schedule are its own).
"""

from __future__ import annotations

import functools

import numpy as np

TABLE_BOUND = 8.0


# -- the data and the fit ----------------------------------------------------


def rows_per_vdpu(cfg: dict) -> int:
    """Rows each vDPU holds: the rows split evenly, the last vDPUs
    filled up with padding rows that count for nothing."""
    return -(-cfg["data"]["rows"] // cfg["n_vdpus"])


def generate(cfg: dict, key, rows_sharding):
    """(X, y) made on the device in one program."""
    import jax

    from bench.datasets import GENERATORS

    d = cfg["data"]
    gen = GENERATORS[d["generator"]]
    fn = jax.jit(lambda k: gen(k, d["rows"], d["features"])[:2],
                 out_shardings=(rows_sharding, rows_sharding))
    return fn(key)


def fit_kwargs(traffic: dict, seed: int) -> dict:
    kw = {"steps": traffic["steps"]}
    if traffic.get("batch_size"):
        kw["batch_size"] = traffic["batch_size"]
        kw["sample_seed"] = seed % 2 ** 31
    return kw


def answer(res) -> dict:
    """What a fit returns, on the host: the weights and each step's
    loss."""
    import jax

    hist = jax.device_get([h["loss"] for h in res.history])
    return {"state": np.asarray(jax.device_get(res.state), np.float64),
            "loss": np.asarray(hist, np.float64)}


# -- the work of one local step ------------------------------------------------


def work(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes of one local step on one chip (its vDPUs), counted
    from the cell's shapes (no padding, no int8 limbs).

    ``step``: what the step must read once (int8 rows, float32 labels
    and row mask) and its int8 products (logits and gradient, 2 ops a
    multiply-add each).  ``fxp_matmul``: the two integer products, each
    of which reads the rows, plus the 16-bit vector each consumes or
    the int32 logits it writes.
    """
    d = cfg["data"]["features"]
    chips = cfg["chips"]
    rows = (cfg["n_vdpus"] // chips) * (traffic.get("batch_size")
                                        or rows_per_vdpu(cfg))
    rows = min(rows, cfg["data"]["rows"] // chips)
    products = 2 * 2 * rows * d
    return {
        "step": {"ops": {"int8": products},
                 "bytes": rows * d + 4 * rows + 4 * rows},
        "fxp_matmul": {"ops": {"int8": products},
                       "bytes": 2 * rows * d + 4 * rows + 2 * rows},
    }


# -- the plain reference ---------------------------------------------------------


def _table(n_entries: int) -> np.ndarray:
    xs = np.linspace(-TABLE_BOUND, TABLE_BOUND, n_entries, dtype=np.float64)
    return (1.0 / (1.0 + np.exp(-xs))).astype(np.float32)


@functools.lru_cache(maxsize=1)
def _epoch_slots(per: int, b: int, seed: int, epoch: int):
    """The slots of one epoch in the order its steps take them, and
    their 0/1 mask: a fresh permutation of the ``per`` slots drawn from
    fold_in(PRNGKey(seed), epoch), filled up to whole batches with
    repeated slots masked out."""
    import jax

    E = -(-per // b)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    perm = np.asarray(jax.random.permutation(key, per), np.int32)
    pad = E * b - per
    if pad:
        perm = np.concatenate([perm, perm[:pad]])
    return perm, (np.arange(E * b) < per).astype(np.float32)


def _batch_slots(per: int, b: int, seed: int, step: int):
    """Rows of each vDPU that step ``step`` uses, and their 0/1 mask:
    epochs of ceil(per / b) steps, each over its own permutation."""
    epoch, pos = divmod(step, -(-per // b))
    perm, valid = _epoch_slots(per, b, seed, epoch)
    return perm[pos * b:(pos + 1) * b], valid[pos * b:(pos + 1) * b]


def reference(cfg: dict, traffic: dict, X, y, seed: int, *,
              data_bits: int = 8, drop_half: bool = False,
              no_exchange: bool = False) -> dict:
    """The fit as the algorithm defines it, from the raw rows.

    ``data_bits=4`` is the control (the next precision below int8).
    Planted faults: ``drop_half`` leaves the second half of every
    vDPU's rows out and takes the mean over the rest; ``no_exchange``
    leaves the sum between chips out, so that the weights (those of the
    first chip) step on the first chip's vDPUs alone, divided by all the
    rows as before.
    """
    import jax
    import jax.numpy as jnp

    est = cfg["estimator"]
    V, per = cfg["n_vdpus"], rows_per_vdpu(cfg)
    n, d = cfg["data"]["rows"], cfg["data"]["features"]
    lr, l2 = est["lr"], est.get("l2", 0.0)
    table = jnp.asarray(_table(est["lut_entries"]))
    step_x = 2 * TABLE_BOUND / (est["lut_entries"] - 1)
    hi = jax.lax.Precision.HIGHEST
    qd = 2 ** (data_bits - 1) - 1
    q16 = 2 ** 15 - 1

    @jax.jit
    def place(X, y):
        amax = jnp.max(jnp.abs(X), axis=0)
        scale = jnp.maximum(amax, 1e-12) / qd
        Xq = jnp.clip(jnp.round(X / scale), -qd - 1, qd)
        pad = V * per - n
        m = jnp.ones((n,), jnp.float32)
        if pad:
            Xq = jnp.concatenate([Xq, jnp.zeros((pad, d), Xq.dtype)])
            y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
            m = jnp.concatenate([m, jnp.zeros((pad,), m.dtype)])
        m = m.reshape(V, per)
        if drop_half:
            m = m.at[:, per // 2:].set(0.0)
        return Xq.reshape(V, per, d), y.reshape(V, per), m, scale

    @jax.jit
    def partials(Xq, y, m, scale, w, mult):
        ws = w * scale
        s_w = jnp.maximum(jnp.max(jnp.abs(ws)), 1e-12) / q16
        wq = jnp.clip(jnp.round(ws / s_w), -q16 - 1, q16)
        z = jnp.einsum("vpd,d->vp", Xq, wq, precision=hi) * s_w
        idx = jnp.clip(jnp.round((z + TABLE_BOUND) / step_x), 0,
                       table.shape[0] - 1).astype(jnp.int32)
        r = (table[idx] - y) * m
        s_r = jnp.maximum(jnp.max(jnp.abs(r), axis=1), 1e-12) / q16
        rq = jnp.clip(jnp.round(r / s_r[:, None]), -q16 - 1, q16)
        g = (jnp.einsum("vpd,vp->vd", Xq, rq, precision=hi)
             * (scale[None, :] * s_r[:, None]))
        pe = jnp.clip(jax.nn.sigmoid(z), 1e-7, 1 - 1e-7)
        loss = -jnp.sum(m * (y * jnp.log(pe) + (1 - y) * jnp.log(1 - pe)),
                        axis=1)
        return g * mult[:, None], loss * mult

    @jax.jit
    def batch_partials(Xq, y, m, slots, valid, scale, w, mult):
        return partials(Xq[:, slots], y[:, slots], m[:, slots] * valid,
                        scale, w, mult)

    Xq, y3, m, scale = place(X, y)
    n_eff = float(np.asarray(jnp.sum(m)))
    # the vDPUs whose partials the merge sums: all, or the first chip's
    merged = V // cfg["chips"] if no_exchange else V
    w = np.zeros((d,), np.float64)
    losses = []
    b = traffic.get("batch_size")
    for t in range(traffic["steps"]):
        if b:
            slots, valid = _batch_slots(per, b, seed % 2 ** 31, t)
            mult = np.full((V,), per / max(valid.sum(), 1.0), np.float32)
            g, loss = batch_partials(Xq, y3, m, slots, valid, scale,
                                     jnp.asarray(w, jnp.float32), mult)
        else:
            g, loss = partials(Xq, y3, m, scale, jnp.asarray(w, jnp.float32),
                               jnp.ones((V,), jnp.float32))
        g = np.asarray(g, np.float64)[:merged].sum(axis=0)
        losses.append(float(np.asarray(loss, np.float64)[:merged].sum())
                      / n_eff)
        w = (w - lr * (g / n_eff + l2 * w)).astype(np.float32)
    return {"state": np.asarray(w, np.float64),
            "loss": np.asarray(losses, np.float64)}


# -- the comparison ----------------------------------------------------------------


def compare(ans: dict, ref: dict) -> dict:
    """``w_gap``: the largest gap of a weight, over the largest weight
    of the reference.  ``loss_gap``: the largest relative gap of a
    step's loss."""
    w_gap = (np.max(np.abs(ans["state"] - ref["state"]))
             / np.max(np.abs(ref["state"])))
    loss_gap = np.max(np.abs(ans["loss"] - ref["loss"]) / np.abs(ref["loss"]))
    return {"w_gap": float(w_gap), "loss_gap": float(loss_gap)}


def initial_state(cfg: dict, X) -> np.ndarray:
    return np.zeros((cfg["data"]["features"],), np.float64)


CONTROL = {"data_bits": 4}


def faults(cfg: dict) -> dict:
    """The planted faults a cell of ``cfg`` can have, as keyword
    arguments of :func:`reference`: the exchange between chips only
    where there is more than one chip."""
    out = {"half_batch": {"drop_half": True}}
    if cfg["chips"] > 1:
        out["no_exchange"] = {"no_exchange": True}
    return out
