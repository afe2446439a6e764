"""HotKeyCache — learned-path-aware read-through cache.

Caches (key -> value row) for keys the snapshot/memtable path already
answered, so a hot key skips the whole lookup stack on its next GET.
Correctness comes from two invalidation rules, both visible in
``stats()``:

* **epoch** — every entry is stamped with its owning shard's structural
  epoch (``ShardedStore.shard_epochs()``: the flush/compaction event
  count that also versions the device state).  A probe whose entry
  carries a stale epoch drops it and misses: any memtable roll or
  compaction on the shard — including one triggered by value-log GC —
  conservatively flushes that shard's cached keys.
* **write** — PUT/DELETE batches flowing through the server explicitly
  drop their keys (an overwrite that stays in the memtable bumps no
  epoch, so the epoch rule alone would serve stale data).

Only *positive* results are cached — a not-found is never remembered, so
a fresh insert can't be shadowed by a stale negative.  Writes that
bypass the server (direct store calls) are outside the contract: route
all writes through the front end.

Storage is row-oriented numpy: one values matrix and parallel key/
epoch/shard/stamp vectors, a row each (keys are non-negative; key -1
marks a free row).  Rows are found through a sorted index: the keys in
ascending order and, beside them, each key's row.  A probe is one
``np.searchsorted`` over it plus an equality check.  An eviction,
invalidation or stale-epoch drop frees its rows and marks their index
entries released (row -1) in place; a fill merges its new keys in at
their sorted positions, and drops the released entries by mask once
they make up an eighth of the slots.  So the index stays sorted without
a re-sort, a small batch pays for no copy of the index beyond its merge,
and no call walks its keys in Python: the cache sits on the serving hot
path, where the pipelined server overlaps host admission with device
compute, so its host cost must stay small.  Recency is tracked with a
per-batch clock stamp and eviction takes the oldest-stamped rows in bulk
(batch-granular LRU).
"""

from __future__ import annotations

import numpy as np

__all__ = ["HotKeyCache"]

# the stamp of a free row: newer than every live row's, so eviction
# never picks one
_FREE = np.iinfo(np.int64).max


class HotKeyCache:
    def __init__(self, slots: int = 4096) -> None:
        self.slots = int(slots)
        self._key = np.full(self.slots, -1, np.int64)    # -1 = free row
        self._epoch = np.zeros(self.slots, np.int64)
        self._shard = np.zeros(self.slots, np.int32)
        self._stamp = np.full(self.slots, _FREE, np.int64)
        self._vals: np.ndarray | None = None     # (slots, V), first fill
        # the key -> row index: keys ascending, each key's row beside;
        # a released key keeps its entry, with row -1, until a merge
        # drops it (a key admitted again lands before that entry)
        self._ikeys = np.empty(0, np.int64)
        self._irows = np.empty(0, np.int64)
        self._live = 0                           # cached keys
        self._clock = 0
        self.hits = 0
        self.misses = 0
        self.fills = 0
        self.evictions = 0
        self.inval_epoch = 0
        self.inval_write = 0

    def __len__(self) -> int:
        return self._live

    def _rows(self, keys: np.ndarray) -> np.ndarray:
        """Row of each key, -1 where the key is not cached."""
        n = self._ikeys.shape[0]
        if n == 0:
            return np.full(keys.shape[0], -1, np.int64)
        pos = self._ikeys.searchsorted(keys)
        np.minimum(pos, n - 1, out=pos)
        rows = self._irows[pos]
        rows[self._ikeys[pos] != keys] = -1
        return rows

    def _release(self, rows: np.ndarray) -> None:
        """Free live ``rows``: their index entries take row -1."""
        self._irows[self._ikeys.searchsorted(self._key[rows])] = -1
        self._key[rows] = -1
        self._stamp[rows] = _FREE
        self._live -= rows.shape[0]

    def _merge(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Merge the uncached ``keys`` (distinct) at ``rows`` into the
        index, first dropping the released entries by mask once they make
        up an eighth of the slots."""
        ik, ir = self._ikeys, self._irows
        if ik.shape[0] - self._live > self.slots // 8:
            keep = ir >= 0
            ik, ir = ik[keep], ir[keep]
        order = keys.argsort()
        keys = keys[order]
        # each new key's place in the merged index: its insertion point,
        # plus the new keys before it
        dst = ik.searchsorted(keys)
        dst += np.arange(dst.shape[0])
        new = np.zeros(ik.shape[0] + dst.shape[0], bool)
        new[dst] = True
        old = ~new
        self._ikeys = np.empty(new.shape[0], np.int64)
        self._ikeys[dst] = keys
        self._ikeys[old] = ik
        self._irows = np.empty(new.shape[0], np.int64)
        self._irows[dst] = rows[order]
        self._irows[old] = ir
        self._live += keys.shape[0]

    def lookup(self, keys: np.ndarray, epochs: tuple,
               out: np.ndarray) -> np.ndarray:
        """Probe the cache; hit rows are written into ``out`` in place.
        Returns the (B,) hit mask.  ``epochs`` is the fleet's current
        epoch vector — entries stamped under an older epoch are dropped
        here (lazy invalidation) and report as misses.  Keys within one
        probe must be unique (the batcher dedups)."""
        n = keys.shape[0]
        hit = np.zeros(n, bool)
        if self._vals is None:
            self.misses += n
            return hit
        rows = self._rows(np.asarray(keys, np.int64))
        have = rows >= 0
        if have.any():
            r = rows[have]
            fresh = (self._epoch[r]
                     == np.asarray(epochs, np.int64)[self._shard[r]])
            stale = r[~fresh]
            if stale.shape[0]:
                self._release(stale)
                self.inval_epoch += int(stale.shape[0])
            live = np.nonzero(have)[0][fresh]
            out[live] = self._vals[r[fresh]]
            hit[live] = True
            self._clock += 1
            self._stamp[r[fresh]] = self._clock
        n_hit = int(np.count_nonzero(hit))
        self.hits += n_hit
        self.misses += n - n_hit
        return hit

    def fill(self, keys: np.ndarray, values: np.ndarray,
             owners: np.ndarray, epochs: tuple) -> None:
        """Admit found (key, value) pairs read under ``epochs``.  Keys
        within one fill must be unique (the batcher dedups)."""
        n = keys.shape[0]
        if n == 0:
            return
        if n > self.slots:
            # a fill larger than the cache: only the last ``slots`` pairs
            # could survive anyway (sequential insertion would evict the
            # rest), so admit exactly those and count the drop
            self.evictions += n - self.slots
            self.fills += n - self.slots
            keys = keys[-self.slots:]
            values = values[-self.slots:]
            owners = owners[-self.slots:]
            n = self.slots
        if self._vals is None:
            self._vals = np.zeros((self.slots, values.shape[1]),
                                  values.dtype)
        self._clock += 1
        keys = np.asarray(keys, np.int64)
        rows = self._rows(keys)
        new = rows < 0
        # the rows this fill updates take the newest stamp first, so the
        # pick below never evicts one (that would hand the row to a new
        # key and then overwrite it with the old key's value: wrong data
        # served for the new key)
        self._stamp[rows[~new]] = self._clock
        n_new = int(np.count_nonzero(new))
        if n_new:
            take = np.empty(0, np.int64)
            if self._live < self.slots:          # free rows first
                take = np.flatnonzero(self._key < 0)[:n_new]
            need = n_new - take.shape[0]
            if need > 0:
                # bulk-evict the oldest-stamped live rows: free rows
                # (stamped _FREE) and the rows this fill updates (stamped
                # the clock above) are the newest, so none is picked
                gone = self._stamp.argpartition(need - 1)[:need]
                self._release(gone)
                self.evictions += need
                take = np.concatenate((take, gone))
            admit = keys[new]
            self._merge(admit, take)
            rows[new] = take
            self._key[take] = admit
        ep = np.asarray(epochs, np.int64)
        ow = np.asarray(owners, np.int64)
        self._vals[rows] = values
        self._shard[rows] = ow
        self._epoch[rows] = ep[ow]
        self._stamp[rows] = self._clock
        self.fills += n

    def invalidate(self, keys: np.ndarray) -> int:
        """Drop keys a write batch superseded; returns how many were
        actually cached."""
        keys = np.unique(np.asarray(keys, np.int64))
        rows = self._rows(keys)
        have = rows >= 0
        n = int(np.count_nonzero(have))
        if n:
            self._release(rows[have])
        self.inval_write += n
        return n

    def stats(self) -> dict:
        probes = self.hits + self.misses
        return {
            "slots": self.slots,
            "entries": len(self),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / max(probes, 1),
            "fills": self.fills,
            "evictions": self.evictions,
            "inval_epoch": self.inval_epoch,
            "inval_write": self.inval_write,
        }
