"""Synthetic FEMNIST for the benchmark: writer-non-IID 28x28 glyphs.

The benchmark's own copy of the program's generator, vectorised over each
client's samples. It keeps the same distributions (47 smooth stroke
prototypes from a cosine basis, one writer style per client, per-sample
angle and scale jitter, pixel noise, Dirichlet class histograms, 200-350
training samples) and the LEAF FEMNIST writer split (arXiv:1812.01097):
one client is one writer. Its numbers need not match the program's bit
for bit; they depend only on `seed` and the mix's `data` block.
"""
from __future__ import annotations

import numpy as np

IMG = 28
N_CLASSES = 47


def class_prototypes(n_classes: int = N_CLASSES) -> np.ndarray:
    """(C, 28, 28) stroke-like prototypes, shared by every seed."""
    rng = np.random.default_rng(4242)
    f = 4
    yy, xx = np.meshgrid(np.arange(IMG), np.arange(IMG), indexing="ij")
    basis = np.stack([np.cos(np.pi * (i + 0.5) * yy / IMG)
                      * np.cos(np.pi * (j + 0.5) * xx / IMG)
                      for i in range(f) for j in range(f)])
    # Classes share a low-rank structure so they stay confusable.
    common = rng.normal(size=(4, f * f)) * 2.0
    mix = rng.normal(size=(n_classes, 4)) / np.sqrt(4)
    coef = mix @ common + rng.normal(size=(n_classes, f * f)) * 0.9
    proto = np.einsum("cb,bhw->chw", coef, basis)
    return np.tanh(np.maximum(proto - 0.3, 0.0) * 2.0).astype(np.float32)


def _render(proto: np.ndarray, labels: np.ndarray, rng: np.random.Generator
            ) -> np.ndarray:
    """One writer's glyphs for `labels`: (n, 28, 28) float32 in [0, 1]."""
    angle, scale, shear = (rng.uniform(-0.45, 0.45), rng.uniform(0.8, 1.25),
                           rng.uniform(-0.3, 0.3))
    tx, ty = rng.uniform(-3.0, 3.0, size=2)
    gain = rng.uniform(0.6, 1.3)
    ew = rng.normal(size=(2, 3)) * 2.0
    ph = rng.uniform(0, 2 * np.pi, size=(2, 3))
    fr = rng.uniform(0.5, 1.5, size=(2, 3))
    n = labels.shape[0]
    f32 = np.float32
    a = (angle + rng.normal(size=n) * 0.1).astype(f32)[:, None, None]
    s = (scale * (1 + rng.normal(size=n) * 0.06)).astype(f32)[:, None, None]
    c0 = (IMG - 1) / 2.0
    yy, xx = np.meshgrid(np.arange(IMG), np.arange(IMG), indexing="ij")
    # Elastic deformation: one smooth field per writer, shared by its rows.
    ex = sum(ew[1, i] * np.sin(fr[1, i] * np.pi * yy / IMG + ph[1, i])
             for i in range(3)).astype(f32)
    ey = sum(ew[0, i] * np.sin(fr[0, i] * np.pi * xx / IMG + ph[0, i])
             for i in range(3)).astype(f32)
    y = ((yy - c0).astype(f32) / s)
    x = ((xx - c0).astype(f32) / s)
    xs = x + f32(shear) * y
    ca, sa = np.cos(a), np.sin(a)
    xr = ca * xs - sa * y + f32(c0 - tx) + ex
    yr = sa * xs + ca * y + f32(c0 - ty) + ey
    x0 = np.clip(np.floor(xr), 0, IMG - 2)
    y0 = np.clip(np.floor(yr), 0, IMG - 2)
    wx = np.clip(xr - x0, 0.0, 1.0)
    wy = np.clip(yr - y0, 0.0, 1.0)
    # Bilinear sample through flat indices into each row's prototype.
    flat = proto.reshape(-1)
    i00 = (labels[:, None, None] * (IMG * IMG)
           + y0.astype(np.int32) * IMG + x0.astype(np.int32))
    img = ((1 - wy) * ((1 - wx) * flat[i00] + wx * flat[i00 + 1])
           + wy * ((1 - wx) * flat[i00 + IMG] + wx * flat[i00 + IMG + 1]))
    img = f32(gain) * img + rng.standard_normal(img.shape, f32) * f32(0.15)
    return np.clip(img, 0.0, 1.0)


def generate(n_clients: int, seed: int, cfg: dict, *,
             min_samples: int = 200, max_samples: int = 350,
             eval_samples: int = 64, dirichlet_alpha: float = 1.0
             ) -> dict[str, np.ndarray]:
    """Stacked client shards, padded to `max_samples` rows.

    Returns x (K, N, 28, 28, 1), y (K, N), n (K,), and the held-out
    x_eval, y_eval, n_eval, all as numpy arrays. The configuration `cfg`
    has to take these glyphs and classes.
    """
    if cfg["classes"] != N_CLASSES or list(cfg["image"]) != [IMG, IMG, 1]:
        raise ValueError(f"configuration {cfg['name']!r} takes "
                         f"{cfg['image']} inputs of {cfg['classes']} classes;"
                         f" this generator makes [{IMG}, {IMG}, 1] glyphs of "
                         f"{N_CLASSES}")
    proto = class_prototypes()
    N = max_samples
    x = np.zeros((n_clients, N, IMG, IMG, 1), np.float32)
    y = np.zeros((n_clients, N), np.int32)
    n = np.zeros((n_clients,), np.int32)
    xe = np.zeros((n_clients, eval_samples, IMG, IMG, 1), np.float32)
    ye = np.zeros((n_clients, eval_samples), np.int32)
    for k in range(n_clients):
        rng = np.random.default_rng(np.random.SeedSequence([seed, k]))
        probs = rng.dirichlet(np.full(N_CLASSES, dirichlet_alpha))
        nk = int(rng.integers(min_samples, max_samples + 1))
        labels = rng.choice(N_CLASSES, size=nk + eval_samples, p=probs)
        imgs = _render(proto, labels, rng)
        x[k, :nk, :, :, 0] = imgs[:nk]
        y[k, :nk] = labels[:nk]
        n[k] = nk
        xe[k, :, :, :, 0] = imgs[nk:]
        ye[k] = labels[nk:]
    ne = np.full((n_clients,), eval_samples, np.int32)
    return dict(x=x, y=y, n=n, x_eval=xe, y_eval=ye, n_eval=ne)
