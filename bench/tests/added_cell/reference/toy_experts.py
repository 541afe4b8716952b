"""The plain reference of the toy expert layer, in float64 on the host;
``mode`` other than "highest" rounds the inputs to bfloat16 first (the
control)."""

import jax.numpy as jnp
import numpy as np


def forward(config, w, x, routes, mode="highest"):
    if mode != "highest":
        w, x = (jnp.asarray(a, jnp.bfloat16) for a in (w, x))
    w, x = np.asarray(w, np.float64), np.asarray(x, np.float64)
    routes = np.asarray(routes)
    held = routes < config["num_experts"]
    y = np.einsum("nd,ndk->nk", x, w[np.where(held, routes, 0)])
    return y * held[:, None]


def gap(got, want) -> float:
    """Largest absolute difference over the reference's largest value."""
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))
