"""Plain reference of a MACH linear classifier (arXiv:1810.04254, §3).

R independent B-way logistic regressions over d features on labels
hashed by R 2-universal hash functions, trained jointly with Adam, and
decoded with the unbiased estimator of Eq. 2

    p_i = B / (B - 1) * (mean_j P^j[h_j(i)] - 1 / B).

Straightforward ``jax.numpy`` in float32: CSR batches are densified,
the projection is one matrix product, the loss is log-softmax, the
gradients come from ``jax.grad``, the scores of all K classes are
materialised.  It imports nothing of the program under test and takes
nothing it made: the hash functions are evaluated from their definition
(multiply-shift, the coefficients drawn from the configuration's hash
seed), and weights and batches come from the benchmark's generator.

``mode`` picks the matrix-product precision: ``"highest"`` is float32
(the reference), ``"bf16_3x"`` the nearest precision below it, three
bfloat16 passes: ``Precision.HIGH`` on a TPU, and written out with the
operands split by bit masks elsewhere, since a CPU ignores the
precision argument (the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# --- hashing -----------------------------------------------------------

def hash_coeffs(config: dict) -> np.ndarray:
    """Multiply-shift: R random odd 32-bit multipliers from the seed."""
    if config["hash"] != "mult_shift":
        raise ValueError(f"reference knows mult_shift hashing only, got "
                         f"{config['hash']!r}")
    b = config["num_buckets"]
    if b & (b - 1):
        raise ValueError("multiply-shift needs a power-of-two B")
    rng = np.random.default_rng(np.random.SeedSequence(
        [config["hash_seed"], 0x5F7]))
    a = rng.integers(0, 1 << 31, size=config["num_repetitions"],
                     dtype=np.uint32)
    return a.astype(np.uint32) * np.uint32(2) + np.uint32(1)


def hash_ids(config: dict, ids: jnp.ndarray) -> jnp.ndarray:
    """(...,) class ids -> (..., R) buckets: (a_j * i mod 2^32) >> (32 -
    log2 B)."""
    shift = 32 - int(np.log2(config["num_buckets"]))
    a = jnp.asarray(hash_coeffs(config))
    prod = ids.astype(jnp.uint32)[..., None] * a
    return jax.lax.shift_right_logical(prod, jnp.uint32(shift)).astype(
        jnp.int32)


# --- matrix products ---------------------------------------------------

def _split(a):
    """a = hi + lo + rest, hi and lo bfloat16 values (the high 16 bits of
    a and of a - hi), by bit masks that no compiler folds away."""
    mask = jnp.uint32(0xFFFF0000)

    def top(v):
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v, jnp.uint32) & mask, jnp.float32)

    hi = top(a)
    return hi, top(a - hi)


def _dot3(a, b):
    if jax.default_backend() == "tpu":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGH)
    (ah, al), (bh, bl) = _split(a), _split(b)
    d = functools.partial(jnp.dot, precision=HIGHEST)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


@jax.custom_vjp
def dot_bf16_3x(a, b):
    return _dot3(a, b)


def _dot3_fwd(a, b):
    return _dot3(a, b), (a, b)


def _dot3_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


dot_bf16_3x.defvjp(_dot3_fwd, _dot3_bwd)


def matmul(mode: str):
    if mode == "highest":
        return functools.partial(jnp.dot, precision=HIGHEST)
    if mode == "bf16_3x":
        return dot_bf16_3x
    raise ValueError(f"mode must be highest or bf16_3x, got {mode!r}")


# --- model -------------------------------------------------------------

def features(config: dict, batch: dict) -> jnp.ndarray:
    """The batch as a dense (N, d) float32 array (CSR rows scatter-add)."""
    if "x" in batch:
        return batch["x"]
    indptr, idx, vals = batch["indptr"], batch["indices"], batch["values"]
    n = indptr.shape[0] - 1
    rows = jnp.repeat(jnp.arange(n), jnp.diff(indptr),
                      total_repeat_length=idx.shape[0])
    return jnp.zeros((n, config["dim"]), jnp.float32).at[rows, idx].add(vals)


def logits(config: dict, params: dict, x: jnp.ndarray, mode: str):
    r, b = config["num_repetitions"], config["num_buckets"]
    w = params["w"].reshape(config["dim"], r * b)
    z = matmul(mode)(x, w) + params["b"].reshape(-1)
    return z.reshape(x.shape[0], r, b)


def loss(config: dict, params: dict, batch: dict, mode: str,
         rows: int | None = None) -> jnp.ndarray:
    """Mean over the batch of the summed R-head cross-entropy on hashed
    labels; ``rows`` keeps only the first rows (a fault of the check)."""
    x, y = features(config, batch), batch["y"]
    if rows is not None:
        x, y = x[:rows], y[:rows]
    z = logits(config, params, x, mode)
    logp = z - jax.nn.logsumexp(z, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, hash_ids(config, y)[..., None], -1)
    return -jnp.mean(jnp.sum(picked[..., 0], axis=-1))


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6), donate_argnums=(1, 2))
def _train_step(config_items, params, opt, batch, mode, rows, t):
    config = dict(config_items)
    value, grads = jax.value_and_grad(
        lambda p: loss(config, p, batch, mode, rows))(params)
    b1, b2, eps = config["b1"], config["b2"], config["eps"]
    lr, wd = config["learning_rate"], config["weight_decay"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt[0], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt[1], grads)
    # bias corrections worked out exactly and rounded once: in float32,
    # 1 - b2**t loses most of its digits to cancellation
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def update(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if p.ndim >= 2:
            u = u + wd * p
        return p - lr * u

    return jax.tree.map(update, params, mu, nu), (mu, nu), value, grads


def train(config: dict, params: dict, batches: list, mode: str = "highest",
          rows: int | None = None):
    """Adam (decoupled weight decay on matrices) over ``batches`` from
    ``params``.  -> (losses, first gradient, parameters after the last
    step)."""
    items = tuple(sorted((k, v) for k, v in config.items()
                         if isinstance(v, (int, float, str))))
    opt = (jax.tree.map(jnp.zeros_like, params),
           jax.tree.map(jnp.zeros_like, params))
    params = jax.tree.map(jnp.copy, params)   # the steps update in place
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        params, opt, value, grads = _train_step(items, params, opt, batch,
                                                mode, rows, t)
        losses.append(float(value))
        if first is None:
            first = grads
    return losses, first, params


# --- decode ------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _topk(config_items, params, batch, mode, k):
    config = dict(config_items)
    r, b = config["num_repetitions"], config["num_buckets"]
    meta = jax.nn.softmax(logits(config, params, features(config, batch),
                                 mode), axis=-1)            # (N, R, B)
    table = hash_ids(config, jnp.arange(config["num_classes"])).T  # (R, K)

    def add(j, acc):
        return acc + jnp.take(meta[:, j, :], table[j], axis=1)

    total = jax.lax.fori_loop(
        0, r, add, jnp.zeros((meta.shape[0], config["num_classes"]),
                             jnp.float32))
    scores = (b / (b - 1.0)) * (total / r - 1.0 / b)        # (N, K)
    val, idx = jax.lax.top_k(scores, k)
    return val, idx, scores


def topk(config: dict, params: dict, batch: dict, k: int,
         mode: str = "highest"):
    """-> (values (N, k), ids (N, k), scores of all classes (N, K)) under
    the unbiased estimator; ties go to the lower class id."""
    if config["estimator"] != "unbiased":
        raise ValueError("reference decodes with the unbiased estimator")
    items = tuple(sorted((k_, v) for k_, v in config.items()
                         if isinstance(v, (int, float, str))))
    return _topk(items, params, batch, mode, k)
