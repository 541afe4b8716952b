"""The one traffic generator: weights and batches on the device, from a seed.

A copy of the generators of ``repro.data.extreme`` (kept here so that
no later change to the program moves the yardstick), rewritten to make
a whole ring of batches in one jitted call:

* dense features (``config["features"] == "dense"``): class centroids
  on the unit sphere, x = normalize(mu_y + noise * eps);
* CSR features (``"csr"``): each class owns ``signature_share * nnz``
  random feature ids (value 1), each row adds Zipf-popular background
  features (value noise * U[0, 1)), rows L2-normalised, every row
  exactly ``nnz`` entries.

Class ids are Zipf(``class_zipf_a``) over the configuration's K.  The
same seed gives the same weights and the same batches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, 64 bits and more included."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


def stream(key: jax.Array, what: str) -> jax.Array:
    """An independent key per use: weights, data, samples."""
    return jax.random.fold_in(key, {"weights": 1, "data": 2,
                                    "sample": 3}[what])


def _zipf_draw(key, n_items: int, a: float, shape) -> jax.Array:
    """Ids in [0, n_items) with P(i) proportional to (i + 1)^-a, by the
    inverse of the cumulative distribution."""
    ranks = jnp.arange(1, n_items + 1, dtype=jnp.float32)
    w = ranks ** (-a)
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(key, shape)
    return jnp.minimum(jnp.searchsorted(cdf, u, side="right"),
                       n_items - 1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("dim", "reps", "buckets",
                                             "w_std", "b_std"))
def make_params(key, *, dim: int, reps: int, buckets: int, w_std: float,
                b_std: float) -> dict:
    """MACH-linear weights: W (d, R, B) ~ N(0, w_std^2), b (R, B) ~
    N(0, b_std^2), float32, made on the device in one call."""
    kw, kb = jax.random.split(key)
    return {"w": w_std * jax.random.normal(kw, (dim, reps, buckets),
                                           jnp.float32),
            "b": b_std * jax.random.normal(kb, (reps, buckets), jnp.float32)}


def head_params(key: jax.Array, config: dict, traffic: dict) -> dict:
    """The cell's MACH-linear weights, from the seed's weights stream."""
    return make_params(stream(key, "weights"), dim=config["dim"],
                       reps=config["num_repetitions"],
                       buckets=config["num_buckets"],
                       w_std=float(traffic["w_std"]),
                       b_std=float(traffic["b_std"]))


@functools.partial(jax.jit, static_argnames=("classes", "dim", "n", "ring",
                                             "zipf_a", "noise"))
def dense_ring(key, *, classes: int, dim: int, n: int, ring: int,
               zipf_a: float, noise: float):
    """-> x (ring, n, d) f32, y (ring, n) int32."""
    kc, kb = jax.random.split(key)
    mu = jax.random.normal(kc, (classes, dim), jnp.float32)
    mu = mu / jnp.linalg.norm(mu, axis=1, keepdims=True)

    def one(k):
        ky, kn = jax.random.split(k)
        y = _zipf_draw(ky, classes, zipf_a, (n,))
        x = mu[y] + noise * jax.random.normal(kn, (n, dim), jnp.float32)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True), y

    return jax.vmap(one)(jax.random.split(kb, ring))


@functools.partial(jax.jit, static_argnames=(
    "classes", "dim", "n", "ring", "nnz", "sig", "zipf_a", "feature_zipf_a",
    "noise"))
def csr_ring(key, *, classes: int, dim: int, n: int, ring: int, nnz: int,
             sig: int, zipf_a: float, feature_zipf_a: float, noise: float):
    """-> indices (ring, n*nnz) int32, values (ring, n*nnz) f32,
    y (ring, n) int32; row r holds entries [r*nnz, (r+1)*nnz)."""
    ks, kb = jax.random.split(key)
    signatures = jax.random.randint(ks, (classes, sig), 0, dim)
    n_bg = nnz - sig

    def one(k):
        ky, kn, kv = jax.random.split(k, 3)
        y = _zipf_draw(ky, classes, zipf_a, (n,))
        ids = signatures[y]
        vals = jnp.ones((n, sig), jnp.float32)
        if n_bg:
            ids = jnp.concatenate(
                [ids, _zipf_draw(kn, dim, feature_zipf_a, (n, n_bg))], 1)
            vals = jnp.concatenate(
                [vals, noise * jax.random.uniform(kv, (n, n_bg))], 1)
        vals = vals / jnp.linalg.norm(vals, axis=1, keepdims=True)
        return ids.reshape(-1), vals.reshape(-1), y

    return jax.vmap(one)(jax.random.split(kb, ring))


def ring(key: jax.Array, config: dict, traffic: dict, n: int):
    """The cell's ring of batches: a list of ``traffic["ring"]`` dicts,
    each {"y": (n,) int32} with "x": (n, d) for dense features or
    "indptr", "indices", "values" (CSR) for sparse ones."""
    size = traffic["ring"]
    common = dict(classes=config["num_classes"], dim=config["dim"], n=n,
                  ring=size, zipf_a=float(traffic["class_zipf_a"]),
                  noise=float(traffic["noise"]))
    if config["features"] == "dense":
        xs, ys = dense_ring(key, **common)
        return [{"x": xs[i], "y": ys[i]} for i in range(size)]
    if config["features"] != "csr":
        raise ValueError(f"features must be dense or csr, got "
                         f"{config['features']!r}")
    nnz = config["nnz"]
    idx, vals, ys = csr_ring(
        key, nnz=nnz, sig=max(1, int(traffic["signature_share"] * nnz)),
        feature_zipf_a=float(traffic["feature_zipf_a"]), **common)
    indptr = jnp.arange(n + 1, dtype=jnp.int32) * nnz
    return [{"indptr": indptr, "indices": idx[i], "values": vals[i],
             "y": ys[i]} for i in range(size)]
