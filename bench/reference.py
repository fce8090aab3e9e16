"""Plain numpy references for the answers the service returns.

Independent of the program: the same semantics written out directly
(the histogram and HyperLogLog apps of the Ditto paper, Table I).  Both
answers use the program's partitioned layout, ``[num_pri, cells / num_pri]``
with global cell ``c`` at ``[c % num_pri, c // num_pri]``.

Each ``Running*`` class folds one session's appends in order and
snapshots the answer a query or close must return at that point.
"""
from __future__ import annotations

import numpy as np

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def murmur3_fmix32(x: np.ndarray) -> np.ndarray:
    """The 32-bit murmur3 finalizer (fmix32) over uint32 keys."""
    h = x.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h = (h * _C1).astype(np.uint32)
    h ^= h >> np.uint32(13)
    h = (h * _C2).astype(np.uint32)
    h ^= h >> np.uint32(16)
    return h


def hll_rho(rest: np.ndarray, width: int) -> np.ndarray:
    """Leading zeros of the low ``width`` bits of ``rest``, plus one."""
    out = np.full(rest.shape, width + 1, np.int64)
    found = np.zeros(rest.shape, bool)
    for b in range(width):
        hit = ((rest >> np.uint32(width - 1 - b)) & np.uint32(1)).astype(bool)
        hit &= ~found
        out[hit] = b + 1
        found |= hit
    return out


def partitioned(flat: np.ndarray, num_pri: int) -> np.ndarray:
    """Flat per-cell answer -> ``[num_pri, ceil(cells / num_pri)]``."""
    per = -(-len(flat) // num_pri)
    padded = np.zeros(per * num_pri, flat.dtype)
    padded[:len(flat)] = flat
    return padded.reshape(per, num_pri).T.copy()


class RunningHisto:
    """Equi-width histogram of ``bins`` bins over ``[0, key_domain)``."""

    def __init__(self, bins: int, key_domain: int, num_pri: int):
        self.bins, self.num_pri = bins, num_pri
        self.width = max(key_domain // bins, 1)
        self.counts = np.zeros(bins, np.int64)

    def add(self, keys: np.ndarray) -> None:
        b = np.minimum(keys.astype(np.int64) // self.width, self.bins - 1)
        self.counts += np.bincount(b, minlength=self.bins)

    def snapshot(self) -> np.ndarray:
        return partitioned(self.counts, self.num_pri)


class RunningHLL:
    """HyperLogLog registers, ``2**p`` of them, murmur3 fmix32 hashing."""

    def __init__(self, p: int, num_pri: int):
        self.p, self.num_pri = p, num_pri
        self.regs = np.zeros(1 << p, np.int64)

    def add(self, keys: np.ndarray) -> None:
        h = murmur3_fmix32(keys)
        reg = (h & np.uint32((1 << self.p) - 1)).astype(np.int64)
        rho = hll_rho(h >> np.uint32(self.p), 32 - self.p)
        np.maximum.at(self.regs, reg, rho)

    def snapshot(self) -> np.ndarray:
        return partitioned(self.regs, self.num_pri)


def running(config: dict):
    """A fresh running reference for one session of ``config``."""
    if config["app"] == "histo":
        return RunningHisto(config["bins"], config["key_domain"],
                            config["num_pri"])
    if config["app"] == "hll":
        return RunningHLL(config["p"], config["num_pri"])
    raise ValueError(f"no reference for app {config['app']!r}")
