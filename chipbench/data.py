"""Corpus, query and insert pools on the device, drawn from the run's seed.

The distribution is the repository's synthetic stand-in for the paper's
Table 1 corpora (`repro.core.datasets`), copied here so the yardstick does
not move with the program: a mixture of Student-t clusters with
heavy-tailed per-dimension scales, queries drawn from the same mixture and
jittered. Here it is drawn with `jax.random` in one jitted call, so a
run's set-up pays no host-side sampling.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (both words count)."""
    seed = int(seed)
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.random.fold_in(key, stream)


def n_clusters(cfg: dict) -> int:
    """Cluster count as `repro.core.datasets` sets it: max(8, sqrt(n) / 2)."""
    return max(8, int(math.sqrt(cfg["n"]) / 2))


def _student_t(key, df: int, shape) -> jax.Array:
    """Student-t with integer `df` as Z / sqrt(chi2_df / df), the chi-square
    a sum of df squared normals: the same law as `jax.random.t`, without
    its per-element gamma rejection loop (whose temporaries alone would
    exceed a chip's memory at Trevi's size)."""
    keys = jax.random.split(key, df + 1)
    chi2 = sum(jnp.square(jax.random.normal(k, shape, jnp.float32))
               for k in keys[1:])
    return jax.random.normal(keys[0], shape, jnp.float32) * jax.lax.rsqrt(
        chi2 / df)


@functools.partial(jax.jit, static_argnames=("n", "d", "n_extra", "clusters",
                                             "df", "nonneg", "center_scale",
                                             "scale_sigma", "jitter"))
def _draw(key, *, n, d, n_extra, clusters, df, nonneg, center_scale,
          scale_sigma, jitter):
    k_c, k_s, k_a, k_t, k_j = jax.random.split(key, 5)
    centers = jax.random.normal(k_c, (clusters, d), jnp.float32) * center_scale
    dim_scale = jnp.exp(jax.random.normal(k_s, (d,), jnp.float32)
                        * scale_sigma)
    total = n + n_extra
    assign = jax.random.randint(k_a, (total,), 0, clusters)
    noise = _student_t(k_t, df, (total, d))
    x = centers[assign] + noise * dim_scale[None, :]
    if nonneg:
        x = jnp.abs(x)
    corpus, extra = x[:n], x[n:]
    extra = extra + jitter * jax.random.normal(k_j, extra.shape, jnp.float32)
    return corpus, extra


def draw(cfg: dict, seed: int, n_extra: int):
    """(corpus (n, d), extra (n_extra, d)) f32 device arrays for `seed`.

    `extra` holds the held-out query pool followed by the insert pool; the
    caller splits it. The same (cfg, seed, n_extra) gives the same arrays.
    """
    g = cfg["generator"]
    return _draw(seed_key(seed), n=int(cfg["n"]), d=int(cfg["d"]),
                 n_extra=int(n_extra), clusters=n_clusters(cfg),
                 df=int(g["df"]), nonneg=bool(g["nonneg"]),
                 center_scale=float(g["center_scale"]),
                 scale_sigma=float(g["dim_scale_sigma"]),
                 jitter=float(g["query_jitter"]))
