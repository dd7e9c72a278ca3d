"""K-means cells: the estimator under test, its data, the work of one
Lloyd iteration, and a plain reference of the same algorithm.

The algorithm (the program's ``KMeans(k, precision="fp32", seed)`` as
the paper describes it): the initial centroids are ``k`` rows drawn
without replacement with ``jax.random.choice(PRNGKey(seed), n, (k,),
replace=False)``.  The rows are split over ``n_vdpus`` vDPUs; each
iteration every vDPU assigns each of its rows to the nearest centroid
and sums the rows, their count and their squared distance per cluster;
the partials are summed over all vDPUs, each centroid becomes the mean
of its rows (an empty cluster keeps its centroid), and the iteration
reports the summed squared distance of the assignment (``sse``).

The reference below follows that description with ``jax.numpy`` and
``numpy`` alone, distances taken as plain squared differences; it
imports nothing of the program and takes nothing the program made.
"""

from __future__ import annotations

import numpy as np

from bench.algos.logreg import fit_kwargs, rows_per_vdpu  # noqa: F401


def generate(cfg: dict, key, rows_sharding):
    import jax

    from bench.datasets import GENERATORS

    d = cfg["data"]
    gen = GENERATORS[d["generator"]]
    fn = jax.jit(lambda k: gen(k, d["rows"], d["features"], d["k"])[0],
                 out_shardings=rows_sharding)
    return fn(key), None


def answer(res) -> dict:
    import jax

    hist = jax.device_get([h["sse"] for h in res.history])
    return {"state": np.asarray(jax.device_get(res.state), np.float64),
            "sse": np.asarray(hist, np.float64)}


def work(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes of one Lloyd iteration on one chip (its vDPUs), from
    the cell's shapes: distances (x . c, 2 ops a multiply-add) and the
    per-cluster sums (2 ops a row and feature of the assignment's one
    hot), reading the float32 rows and the row mask once."""
    d, k = cfg["data"]["features"], cfg["data"]["k"]
    chips = cfg["chips"]
    rows = (cfg["n_vdpus"] // chips) * (traffic.get("batch_size")
                                        or rows_per_vdpu(cfg))
    rows = min(rows, cfg["data"]["rows"] // chips)
    flops = 2 * rows * k * d + 2 * rows * k * d
    w = {"ops": {"f32": flops}, "bytes": 4 * rows * d + 4 * rows}
    return {"step": w, "kmeans_assign": w}


def reference(cfg: dict, traffic: dict, X, y, seed: int, *,
              dtype: str = "float32", drop_half: bool = False) -> dict:
    """Lloyd's iterations as the algorithm defines them, from the raw
    rows.  ``dtype="bfloat16"`` is the control: rows, centroids and
    distances in bfloat16, sums accumulated in float32.  ``drop_half``
    leaves the second half of every vDPU's rows out (a planted fault)."""
    import jax
    import jax.numpy as jnp

    if traffic.get("batch_size"):
        raise NotImplementedError("minibatch k-means has no reference yet")
    est = cfg["estimator"]
    V, per = cfg["n_vdpus"], rows_per_vdpu(cfg)
    n, d, k = cfg["data"]["rows"], cfg["data"]["features"], est["k"]
    dt = jnp.dtype(dtype)
    hi = jax.lax.Precision.HIGHEST

    c = initial_state(cfg, X)

    @jax.jit
    def place(X):
        pad = V * per - n
        m = jnp.ones((n,), jnp.float32)
        if pad:
            X = jnp.concatenate([X, jnp.zeros((pad, d), X.dtype)])
            m = jnp.concatenate([m, jnp.zeros((pad,), m.dtype)])
        m = m.reshape(V, per)
        if drop_half:
            m = m.at[:, per // 2:].set(0.0)
        return X.astype(dt).reshape(V, per, d), m

    @jax.jit
    def partials(X, m, c):
        c = c.astype(dt)
        d2 = jnp.stack([jnp.sum((X - c[j]) ** 2, axis=-1)
                        for j in range(k)], axis=-1)          # (V, per, k)
        a = jnp.argmin(d2, axis=-1)
        onehot = (a[..., None] == jnp.arange(k)).astype(dt) * m[..., None]
        sums = jnp.einsum("vpk,vpd->vkd", onehot, X, precision=hi,
                          preferred_element_type=jnp.float32)
        counts = jnp.sum(onehot.astype(jnp.float32), axis=1)
        sse = jnp.sum(jnp.min(d2, axis=-1).astype(jnp.float32) * m, axis=1)
        return sums, counts, sse

    Xv, m = place(X)
    sses = []
    for _ in range(traffic["steps"]):
        sums, counts, sse = partials(Xv, m, jnp.asarray(c, jnp.float32))
        sums = np.asarray(sums, np.float64).sum(axis=0)
        counts = np.asarray(counts, np.float64).sum(axis=0)
        sses.append(float(np.asarray(sse, np.float64).sum()))
        new = sums / np.maximum(counts, 1.0)[:, None]
        c = np.where(counts[:, None] > 0, new, c).astype(np.float32)
    return {"state": np.asarray(c, np.float64),
            "sse": np.asarray(sses, np.float64)}


def compare(ans: dict, ref: dict) -> dict:
    """``c_gap``: the largest gap of a centroid coordinate, over the
    largest coordinate of the reference.  ``sse_gap``: the largest
    relative gap of an iteration's summed squared distance."""
    c_gap = (np.max(np.abs(ans["state"] - ref["state"]))
             / np.max(np.abs(ref["state"])))
    sse_gap = np.max(np.abs(ans["sse"] - ref["sse"]) / np.abs(ref["sse"]))
    return {"c_gap": float(c_gap), "sse_gap": float(sse_gap)}


def initial_state(cfg: dict, X) -> np.ndarray:
    import jax

    est = cfg["estimator"]
    idx = jax.random.choice(jax.random.PRNGKey(est["seed"]),
                            cfg["data"]["rows"], (est["k"],), replace=False)
    return np.asarray(X[idx], np.float64)


CONTROL = {"dtype": "bfloat16"}


def faults(cfg: dict) -> dict:
    """The planted faults, as keyword arguments of :func:`reference`
    (the k-means configurations run on one chip)."""
    return {"half_batch": {"drop_half": True}}

