"""Data and request distributions, generated from the run's seed.

Copies of the program's generators (``core/datasets.py`` ``osm_like``,
``core/workloads.py`` scrambled zipf, ``chip_smoke.py`` value bytes) kept
here so that a change to the program cannot move the yardstick.  The
zipf's CDF is built once per distribution, not once per draw.
"""

from __future__ import annotations

import numpy as np

KEY_BITS = 62                      # the store's key domain: [0, 2**62)
_KEY_MASK = np.uint64((1 << KEY_BITS) - 1)
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """FNV-1a 64 over the 8 little-endian bytes of each value, as YCSB's
    ``Utils.fnvhash64`` hashes a record index (uint64 arithmetic wraps)."""
    v = np.asarray(x).astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    for i in range(8):
        h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
        h *= _FNV_PRIME
    return h


# ---------------------------------------------------------------- key sets

def osm_keys(n: int, rng: np.random.Generator) -> np.ndarray:
    """OpenStreetMap-like sorted unique keys: a clustered mixture (dense
    cities, sparse rest), a copy of the program's ``osm_like``."""
    n_clusters = max(8, n // 4096)
    centers = np.sort(rng.integers(0, 1 << 50, size=n_clusters,
                                   dtype=np.int64))
    sizes = rng.multinomial(n * 2, rng.dirichlet(np.ones(n_clusters) * 0.3))
    parts = [c + np.abs(rng.standard_normal(s) * 65536).astype(np.int64)
             for c, s in zip(centers, sizes) if s > 0]
    keys = np.unique(np.concatenate(parts))
    while keys.shape[0] < n:           # top up collisions
        extra = rng.integers(0, 1 << 52, size=n, dtype=np.int64)
        keys = np.unique(np.concatenate([keys, extra]))
    return keys[:n]


def ycsb_keys(n: int) -> np.ndarray:
    """YCSB ``insertorder=hashed`` keys: FNV-1a-64 of record index i,
    masked to the store's 62-bit domain, later duplicates dropped;
    returned in insertion (record index) order, a scrambled order."""
    end = n
    while True:
        h = (fnv1a64(np.arange(end, dtype=np.int64)) & _KEY_MASK).astype(
            np.int64)
        _, first = np.unique(h, return_index=True)
        if first.shape[0] >= n:
            return h[np.sort(first)[:n]]
        end += n - first.shape[0] + 16


KEY_SETS = {
    "osm": lambda n, rng: osm_keys(n, rng),
    # SOSD uden: dense, consecutive integers
    "uden": lambda n, rng: np.arange(n, dtype=np.int64),
    "ycsb_hashed": lambda n, rng: ycsb_keys(n),
    # YCSB insertorder=ordered: the key of record i is i
    "ycsb_ordered": lambda n, rng: np.arange(n, dtype=np.int64),
}


# ------------------------------------------------------------------ values

def _multipliers(words: int) -> np.ndarray:
    """Odd 64-bit multipliers, one per value word (splitmix64 of i)."""
    z = (np.arange(1, words + 1, dtype=np.uint64) * _GOLDEN)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (z ^ (z >> np.uint64(31))) | np.uint64(1)


class ValueMaker:
    """Value bytes of (key, write version): word i of a record is
    ``(key ^ salt) * M[i] + version``, so every version of every key has
    its own bytes and a value read back names the write it came from."""

    def __init__(self, value_size: int, salt: int) -> None:
        self.value_size = int(value_size)
        self.words = -(-self.value_size // 8)
        self.mix = _multipliers(self.words)
        self.salt = np.uint64(salt & ((1 << 64) - 1))

    def __call__(self, keys: np.ndarray, version) -> np.ndarray:
        k = np.asarray(keys, np.int64).astype(np.uint64)[:, None] ^ self.salt
        ver = np.asarray(version, np.int64).astype(np.uint64).reshape(-1, 1)
        w = np.ascontiguousarray(k * self.mix[None, :] + ver)
        return w.view(np.uint8)[:, : self.value_size]


# ----------------------------------------------------------- distributions

class Uniform:
    def __init__(self, n: int) -> None:
        self.n = int(n)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return rng.integers(0, self.n, size=size)


class ScrambledZipf:
    """YCSB scrambled zipfian over [0, n): a zipf(theta) rank, then the
    rank's FNV hash modulo n, so the hot items are spread over the key
    space.  The CDF over ranks is built once."""

    def __init__(self, n: int, theta: float = 0.99) -> None:
        self.n = int(n)
        w = 1.0 / np.arange(1, self.n + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size), side="right")
        return np.minimum(r, self.n - 1)

    def scramble(self, ranks: np.ndarray) -> np.ndarray:
        return (fnv1a64(ranks) % np.uint64(self.n)).astype(np.int64)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return self.scramble(self.ranks(rng, size))


def distribution(name: str, n: int, params: dict):
    if name == "uniform":
        return Uniform(n)
    if name == "zipfian":
        return ScrambledZipf(n, float(params.get("zipf_theta", 0.99)))
    raise ValueError(f"unknown request distribution {name!r}")
