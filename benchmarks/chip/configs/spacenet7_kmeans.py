"""Notebook cells of the spacenet7_kmeans configuration.

The paper's remote-sensing notebook (arXiv:2107.00187, section IV), shape
for shape: ``load`` makes the SpaceNet 7 mosaics from the seed, normalizes
them to float32 and computes band histograms and the distance of each scene
to the next; ``filter`` keeps the scenes above a quantile of that distance
and computes their Sobel edges; ``tweak`` sets the number of clusters;
``kmeans`` clusters the pixels of each kept scene (bands and edge) with
Lloyd's algorithm in ``jax.numpy``; ``report`` averages the centroids over
the scenes.
"""
from __future__ import annotations

OUTPUTS = ("centroids", "report")


def cells(config: dict, traffic: dict, keys: dict) -> dict[str, str]:
    """Cell sources by name; ``keys`` holds the 31-bit seeds and the seeded
    parameter sequences the harness derived from ``--seed``."""
    shape = tuple(int(s) for s in config["scene_shape"])
    load = f"""
import numpy as np
import jax
import jax.numpy as jnp

rng = np.random.default_rng({keys["data"]})
lo = rng.integers(0, 96, {int(config["num_scenes"])})
hi = rng.integers(160, 256, {int(config["num_scenes"])})
scenes = [rng.integers(a, b, {shape!r}, dtype=np.{config["scene_dtype"]})
          for a, b in zip(lo, hi)]
normalized = [s.astype(np.float32) / np.float32(255.0) for s in scenes]
counts = [np.bincount(s.reshape(-1), minlength=256).reshape(
    {int(config["histogram_bins"])}, -1).sum(axis=1) for s in scenes]
dists = np.array([np.abs(np.cumsum(a) - np.cumsum(b)).sum()
                  for a, b in zip(counts, counts[1:] + counts[:1])],
                 np.float64)
quantiles = {list(keys["quantiles"])!r}
k_values = {list(keys["k_values"])!r}
k_clusters = k_values[0]
kmeans_k_max = {int(config["kmeans_max_k"])}
kmeans_iters = {int(config["kmeans_iters"])}
fcycle = 0
kcycle = 0


def sobel(img):
    gray = img.mean(axis=-1)
    gx = np.zeros_like(gray)
    gy = np.zeros_like(gray)
    gx[1:-1] = gray[2:] - gray[:-2]
    gy[:, 1:-1] = gray[:, 2:] - gray[:, :-2]
    return np.sqrt(gx ** 2 + gy ** 2)


def lloyd(img, edge, k, k_max, iters):
    x = jnp.concatenate([img.reshape(-1, img.shape[-1]),
                         edge.reshape(-1, 1)], axis=1)
    idx = jnp.linspace(0, x.shape[0] - 1, k_max).astype(jnp.int32)
    valid = jnp.arange(k_max) < k

    def step(_, c):
        d = jnp.sum((x[:, None, :] - c[None, :, :]) ** 2, axis=-1)
        a = jnp.argmin(jnp.where(valid[None, :], d, jnp.inf), axis=1)
        onehot = (a[:, None] == jnp.arange(k_max)[None, :]).astype(
            jnp.float32)
        s = jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGHEST)
        n = onehot.sum(axis=0)
        keep = (n > 0) & valid
        return jnp.where(keep[:, None], s / jnp.maximum(n, 1.0)[:, None], c)

    return jax.lax.fori_loop(0, iters, step, x[idx])


def kmeans_one(img, edge, k, k_max, iters):
    return jax.jit(lloyd, static_argnums=(3, 4))(img, edge, k, k_max, iters)
"""
    filt = """
q = quantiles[fcycle % len(quantiles)]
fcycle += 1
threshold = np.quantile(dists, q)
keep_idx = [i for i, d in enumerate(dists) if d > threshold]
filtered = [normalized[i] for i in keep_idx]
edges = [sobel(f) for f in filtered]
"""
    tweak = """
k_clusters = k_values[kcycle % len(k_values)]
kcycle += 1
"""
    kmeans = """
centroids = np.stack([
    np.asarray(kmeans_one(f, e, k_clusters, kmeans_k_max, kmeans_iters))
    for f, e in zip(filtered, edges)])
"""
    report = """
report = (fcycle, kcycle, k_clusters,
          centroids[:, :k_clusters].mean(axis=0))
"""
    return {"load": load, "filter": filt, "tweak": tweak, "kmeans": kmeans,
            "report": report}
