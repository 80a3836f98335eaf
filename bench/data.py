"""Ridge designs and targets for the benchmark, made on the device from a seed.

A design is the paper's workload (arXiv:1404.0466 §6): two-class Gaussian
inputs pushed through a degree-2 Kar–Karnick random polynomial feature map,
with an intercept column, so there are h columns in all.  The number of raw
inputs is 2·isqrt(h): their degree-2 monomials outnumber the features, so the
design has full column rank (64 raw inputs span only 2145 monomials, which
leaves the h=4096 Hessian singular).  Features are scaled by 1/√n, so λ is
per sample and the paper's grid [1e-3, 1] brackets the hold-out optimum.
Targets are a planted linear model plus unit Gaussian noise.

Nothing here imports the program: the reference is given the same arrays
the program is given, and derives everything else itself.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def raw_dim(h: int) -> int:
    return 2 * math.isqrt(h)


@functools.partial(jax.jit, static_argnames=("n", "h", "n_targets"))
def make_design(key: jax.Array, n: int, h: int, n_targets: int = 1):
    """(x, y): x (n, h) float32 scaled by 1/√n, y (n_targets, n) float32."""
    k_mu, k_x, k_lab, k_w, k_t, k_e = jax.random.split(key, 6)
    d = raw_dim(h)
    mu = jax.random.normal(k_mu, (d,)) / math.sqrt(d)
    sign = jnp.where(jax.random.bernoulli(k_lab, 0.5, (n, 1)), 1.0, -1.0)
    x_raw = jax.random.normal(k_x, (n, d)) + sign * mu
    x1 = jnp.concatenate([jnp.ones((n, 1)), x_raw], axis=1)
    feats = jnp.ones((n, h - 1))
    for t in range(2):                      # degree-2 Kar–Karnick map
        omega = jax.random.rademacher(jax.random.fold_in(k_w, t),
                                      (d + 1, h - 1), jnp.float32)
        feats = feats * jnp.matmul(x1, omega,
                                   precision=jax.lax.Precision.HIGHEST)
    feats = feats / math.sqrt(h - 1)
    x = jnp.concatenate([feats, jnp.ones((n, 1))], axis=1)
    theta = 3.0 * jax.random.normal(k_t, (n_targets, h)) / math.sqrt(h)
    y = jnp.matmul(theta, x.T, precision=jax.lax.Precision.HIGHEST) \
        + jax.random.normal(k_e, (n_targets, n))
    return x / math.sqrt(n), y


def log_grid(lo: float, hi: float, q: int) -> jax.Array:
    """q log-spaced λ on [lo, hi] in float32, as the program is given them."""
    return jnp.logspace(math.log10(lo), math.log10(hi), q, dtype=jnp.float32)
