"""The benchmark's data: the paper's two synthetic image sets, made from a
data seed, and the partitions a job trains on.

The generators are a copy of ``repro.data.synthetic`` (the program's own)
and make the same images to the bit (``test_chipbench_data`` holds them
to it). They render one image at a time, about two minutes for 240,000
images, so ``make`` keeps each set it renders in a cache directory (the
harness gives ``.cache/data/`` of the benchmark in the checkout), named
by generator, size and data seed: only the first run of a configuration
in a checkout renders it.
"""
from __future__ import annotations

import os

import numpy as np

IMG = 28

# strokes ((r0, c0), (r1, c1)) on a 7x7 design grid
GLYPHS = {
    "0": [((1, 2), (1, 4)), ((1, 4), (5, 4)), ((5, 4), (5, 2)), ((5, 2), (1, 2))],
    "1": [((1, 3), (5, 3)), ((1, 3), (2, 2))],
    "2": [((1, 2), (1, 4)), ((1, 4), (3, 4)), ((3, 4), (3, 2)), ((3, 2), (5, 2)), ((5, 2), (5, 4))],
    "3": [((1, 2), (1, 4)), ((3, 2), (3, 4)), ((5, 2), (5, 4)), ((1, 4), (5, 4))],
    "4": [((1, 2), (3, 2)), ((3, 2), (3, 4)), ((1, 4), (5, 4))],
    "5": [((1, 4), (1, 2)), ((1, 2), (3, 2)), ((3, 2), (3, 4)), ((3, 4), (5, 4)), ((5, 4), (5, 2))],
    "6": [((1, 4), (1, 2)), ((1, 2), (5, 2)), ((5, 2), (5, 4)), ((5, 4), (3, 4)), ((3, 4), (3, 2))],
    "7": [((1, 2), (1, 4)), ((1, 4), (5, 2))],
    "8": [((1, 2), (1, 4)), ((1, 4), (5, 4)), ((5, 4), (5, 2)), ((5, 2), (1, 2)), ((3, 2), (3, 4))],
    "9": [((3, 4), (3, 2)), ((3, 2), (1, 2)), ((1, 2), (1, 4)), ((1, 4), (5, 4))],
    "A": [((5, 2), (1, 3)), ((1, 3), (5, 4)), ((3, 2), (3, 4))],
    "B": [((1, 2), (5, 2)), ((1, 2), (1, 4)), ((3, 2), (3, 4)), ((5, 2), (5, 4)), ((1, 4), (3, 4)), ((3, 4), (5, 4))],
    "C": [((1, 4), (1, 2)), ((1, 2), (5, 2)), ((5, 2), (5, 4))],
    "D": [((1, 2), (5, 2)), ((1, 2), (1, 3)), ((5, 2), (5, 3)), ((1, 3), (3, 4)), ((5, 3), (3, 4))],
    "E": [((1, 4), (1, 2)), ((1, 2), (5, 2)), ((5, 2), (5, 4)), ((3, 2), (3, 3))],
    "F": [((1, 4), (1, 2)), ((1, 2), (5, 2)), ((3, 2), (3, 3))],
    "G": [((1, 4), (1, 2)), ((1, 2), (5, 2)), ((5, 2), (5, 4)), ((5, 4), (3, 4)), ((3, 4), (3, 3))],
    "H": [((1, 2), (5, 2)), ((1, 4), (5, 4)), ((3, 2), (3, 4))],
    "I": [((1, 3), (5, 3)), ((1, 2), (1, 4)), ((5, 2), (5, 4))],
    "J": [((1, 2), (1, 4)), ((1, 3), (5, 3)), ((5, 3), (5, 2)), ((5, 2), (4, 2))],
}
NUMERIC = list("0123456789")
ALPHA = list("ABCDEFGHIJ")


def render(glyph: str, rng: np.random.Generator) -> np.ndarray:
    """One 28x28 glyph with its own scale, rotation, shear and shift,
    strokes stamped 2x2 wide."""
    img = np.zeros((IMG, IMG), np.float32)
    scale = 4.0 * (0.8 + 0.4 * rng.random())
    theta = (rng.random() - 0.5) * 0.5
    shear = (rng.random() - 0.5) * 0.3
    dx, dy = rng.integers(-2, 3, size=2)
    ct, st = np.cos(theta), np.sin(theta)
    for (r0, c0), (r1, c1) in GLYPHS[glyph]:
        rr = np.linspace(r0, r1, 24) - 3.0
        cc = np.linspace(c0, c1, 24) - 3.0
        cc = cc + shear * rr
        r = ct * rr - st * cc
        c = st * rr + ct * cc
        ri = np.clip((r * scale + IMG / 2 + dy), 0, IMG - 1.01)
        ci = np.clip((c * scale + IMG / 2 + dx), 0, IMG - 1.01)
        for t in range(24):
            i, j = int(ri[t]), int(ci[t])
            img[i:i + 2, j:j + 2] = 1.0
    return img


def add_noise(images: np.ndarray, kind: str, rng: np.random.Generator
              ) -> np.ndarray:
    """The paper's three extension noises (Fig. 4), clipped to [0, 1]."""
    if kind == "gaussian":
        out = images + rng.normal(0.0, 0.25, images.shape).astype(np.float32)
    elif kind == "salt_pepper":
        out = images.copy()
        m = rng.random(images.shape)
        out[m < 0.05] = 0.0
        out[m > 0.95] = 1.0
    elif kind == "poisson":
        lam = np.clip(images, 0, 1) * 12.0 + 1e-3
        out = rng.poisson(lam).astype(np.float32) / 12.0
    else:
        raise ValueError(f"unknown noise {kind!r}")
    return np.clip(out, 0.0, 1.0)


def _base_set(classes, n_per_class: int, rng, foolish: float = 0.0):
    """``n_per_class`` glyphs of each class, class by class; a ``foolish``
    share of them heavily distorted (the not-MNIST "foolish images")."""
    x = np.stack([render(g, rng) for g in classes for _ in range(n_per_class)])
    y = np.repeat(np.arange(len(classes), dtype=np.int32), n_per_class)
    if foolish > 0:
        pick = rng.choice(len(y), int(len(y) * foolish), replace=False)
        x[pick] = np.clip(x[pick] + rng.normal(0, 0.6, x[pick].shape), 0, 1)
    return x, y


def extended_mnist(n_per_class: int, seed: int):
    """10 numeric glyph classes, extended 3x with the three noises and
    shuffled: every contiguous block shares one distribution."""
    rng = np.random.default_rng(seed)
    x0, y0 = _base_set(NUMERIC, n_per_class, rng)
    xs = [x0] + [add_noise(x0, k, rng)
                 for k in ("gaussian", "salt_pepper", "poisson")]
    x, y = np.concatenate(xs), np.tile(y0, 4)
    idx = rng.permutation(len(x))
    return x[idx].astype(np.float32), y[idx]


def not_mnist(n_per_class: int, seed: int):
    """20 classes, the numeric block then the alphabet block (unshuffled:
    a contiguous partition is class-skewed), with look-alike pairs and 10%
    (numeric) / 15% (alphabet) foolish images."""
    rng = np.random.default_rng(seed)
    xn, yn = _base_set(NUMERIC, n_per_class, rng, foolish=0.1)
    xa, ya = _base_set(ALPHA, n_per_class, rng, foolish=0.15)
    return (np.concatenate([xn, xa]).astype(np.float32),
            np.concatenate([yn, ya + 10]))


GENERATORS = {"extended_mnist": extended_mnist, "not_mnist": not_mnist}


def make(generator: str, n_per_class: int, seed: int, cache: str):
    """(x (n, 28, 28) float32 in [0, 1], y (n,) int32) of one generator,
    read from ``cache`` where an earlier run rendered it."""
    base = os.path.join(cache, f"{generator}-{n_per_class}-{seed}")
    if os.path.exists(base + ".y.npy"):
        return np.load(base + ".x.npy"), np.load(base + ".y.npy")
    x, y = GENERATORS[generator](n_per_class, seed)
    os.makedirs(cache, exist_ok=True)
    for part, a in (("x", x), ("y", y)):      # y last: it marks a whole set
        tmp = f"{base}.{part}.{os.getpid()}.npy"
        np.save(tmp, a)
        os.replace(tmp, f"{base}.{part}.npy")
    return x, y


def held_out(generator: str, n: int, seed: int, num_classes: int,
             cache: str):
    """``n`` images of the same generator under another data seed,
    shuffled: the images a served request carries."""
    per = -(-n // (num_classes if generator == "not_mnist"
                   else 4 * num_classes))
    x, y = make(generator, per, seed, cache)
    idx = np.random.default_rng(seed).permutation(len(x))[:n]
    return x[idx], y[idx]


def partition(x, y, k: int, how: str, seed: int):
    """k equal row blocks: ``iid`` shuffles first (data seed), and
    ``contiguous`` splits the rows as stored."""
    if how not in ("iid", "contiguous"):
        raise ValueError(f"unknown partition {how!r}")
    p = len(x) // k
    idx = (np.random.default_rng(seed).permutation(len(x)) if how == "iid"
           else np.arange(len(x)))
    return [(x[idx[i * p:(i + 1) * p]], y[idx[i * p:(i + 1) * p]])
            for i in range(k)]
