"""The general request generator: YCSB key choosers and arrival processes.

Every request is a list of record ids.  A configuration names the YCSB
operation (``read`` of one record, or ``scan`` of consecutive records) and
its key distribution; a traffic mix names the arrival process (``open``
Poisson at a fixed rate, or ``closed`` with a fixed number of clients).
Everything is drawn from ``--seed`` with numpy: nothing is downloaded.

``scrambled_zipfian`` follows YCSB's ``ScrambledZipfianGenerator``: a
zipfian rank over 10^10 items with constant 0.99 and YCSB's precomputed
zeta, hashed with FNV-1a 64 onto the record space.  It is bounded: no
draw is clipped, so no mass piles on one id.
"""
from __future__ import annotations

import numpy as np

# YCSB ScrambledZipfianGenerator constants (site.ycsb.generator)
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZIPFIAN_CONSTANT = 0.99
YCSB_ZETAN = 26.46902820178302          # zeta(10^10, 0.99), as YCSB ships it
FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of a (possibly > 32-bit) seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  (int(seed) >> 32) & 0xFFFFFFFF, stream])


def zipfian_ranks(rng, size: int, items: int = YCSB_ITEM_COUNT,
                  theta: float = YCSB_ZIPFIAN_CONSTANT,
                  zetan: float = YCSB_ZETAN) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` (Gray et al.), vectorised: ranks
    in ``[0, items)``, rank 0 the most popular."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    r = np.floor(items * np.power(eta * u - eta + 1.0, alpha))
    r = np.where(uz < 1.0 + 0.5 ** theta, 1.0, r)
    r = np.where(uz < 1.0, 0.0, r)
    return np.minimum(r, items - 1).astype(np.int64)


def fnv1a64(vals: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64``: FNV-1a over the 8 little-endian bytes of
    each value, then ``Math.abs`` of the signed result."""
    v = vals.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * FNV_PRIME_64
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian(rng, n: int, size: int) -> np.ndarray:
    """YCSB ``ScrambledZipfianGenerator(0, n-1)``: ids in ``[0, n)``."""
    return (fnv1a64(zipfian_ranks(rng, size)) % n).astype(np.int64)


def uniform_lengths(rng, size: int, lo: int, hi: int) -> np.ndarray:
    """YCSB ``scanlengthdistribution=uniform``: integers in ``[lo, hi]``."""
    return rng.integers(lo, hi + 1, size=size)


class Requests:
    """A seeded stream of requests for one configuration.

    ``next(k)`` returns ``k`` requests as a list of int32 id arrays."""

    def __init__(self, cfg: dict, seed: int, stream: int):
        self.n = int(cfg["recordcount"])
        self.op = cfg["operation"]
        self.rng = rng_for(seed, stream)
        if cfg["requestdistribution"] != "zipfian":
            raise ValueError(cfg["requestdistribution"])
        if self.op not in ("read", "scan"):
            raise ValueError(self.op)
        self.max_scan = int(cfg.get("maxscanlength", 1))
        if self.op == "scan" and cfg["scanlengthdistribution"] != "uniform":
            raise ValueError(cfg["scanlengthdistribution"])

    def next(self, k: int) -> list:
        starts = scrambled_zipfian(self.rng, self.n, k)
        if self.op == "read":
            return [np.array([s], np.int32) for s in starts]
        lens = uniform_lengths(self.rng, k, 1, self.max_scan)
        # a scan past the last record returns the records there are
        return [np.arange(s, min(s + ln, self.n), dtype=np.int32)
                for s, ln in zip(starts, lens)]


def open_arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Poisson arrivals at ``rate`` over ``[0, seconds)``, conditioned on
    their count: ``round(rate * seconds)`` sorted uniform times, so every
    seed offers the same number of requests in another order."""
    n = int(round(rate * seconds))
    return np.sort(rng_for(seed, 7).random(n) * seconds)


def closed_clients(traffic: dict, batch: int) -> int:
    """Clients of a closed loop: ``clients_per_batch_id`` times the engine
    batch (one batch per pipeline slot plus one queued)."""
    return int(traffic["clients_per_batch_id"] * batch)
