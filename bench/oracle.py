"""Plain reference of the store's contents, and the comparison that
decides ``correct``.

A copy of ``chip_smoke.py``'s ``Oracle``: the sorted loaded keys (all at
write version 0) plus a dict of every later write (its version, or None
for a delete).  It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


class Oracle:
    def __init__(self, keys: np.ndarray) -> None:
        self.keys = np.sort(np.asarray(keys, np.int64))
        self.later: dict[int, int | None] = {}

    def put(self, keys, version: int) -> None:
        self.later.update((int(k), int(version)) for k in np.asarray(keys))

    def delete(self, keys) -> None:
        self.later.update((int(k), None) for k in np.asarray(keys))

    def present(self, probes: np.ndarray) -> np.ndarray:
        probes = np.asarray(probes, np.int64)
        idx = np.clip(np.searchsorted(self.keys, probes), 0,
                      self.keys.shape[0] - 1)
        return self.keys[idx] == probes

    def expect(self, probes) -> tuple[np.ndarray, np.ndarray]:
        """(found, write version) of each probe in the current state."""
        probes = np.asarray(probes, np.int64)
        found = self.present(probes)
        ver = np.zeros(probes.shape[0], np.int64)
        if self.later:
            for i, p in enumerate(probes.tolist()):
                if p in self.later:
                    v = self.later[p]
                    found[i] = v is not None
                    ver[i] = 0 if v is None else v
        return found, ver


def wrong_keys(want_found: np.ndarray, want_vals: np.ndarray,
               found: np.ndarray, vals: np.ndarray) -> int:
    """Keys whose found flag, or (when found) any value byte, differs."""
    bad = np.asarray(found, bool) != want_found
    bad |= want_found & np.any(np.asarray(vals) != want_vals, axis=1)
    return int(bad.sum())


class Checker:
    """Replays the run's writes in submission order and compares each
    sampled GET answer with the reference state at its submission.

    The server never reorders a GET around a write (writes are pipeline
    barriers, batches never mix ops), so the state a GET must see is
    exactly the writes submitted before it."""

    def __init__(self, oracle: Oracle, make_values) -> None:
        self.oracle = oracle
        self.make_values = make_values
        self.events: list = []          # (seq, kind, payload)
        # (key, want found, want version, got found, want and got value
        # prefixes) of each wrong key, for the run's log
        self.wrong_detail: list = []

    def write(self, seq: int, op: str, keys, version: int) -> None:
        self.events.append((seq, op, (np.asarray(keys, np.int64), version)))

    def read(self, seq: int, keys, found, vals) -> None:
        self.events.append((seq, "get", (np.asarray(keys, np.int64),
                                         np.asarray(found, bool),
                                         np.asarray(vals, np.uint8))))

    def replay(self, after_seq: int | None = None) -> tuple:
        """Returns (keys compared, keys wrong, reads with a wrong key, keys
        wrong in reads submitted at or after ``after_seq``)."""
        compared = wrong = wrong_reads = wrong_after = 0
        for seq, kind, p in sorted(self.events, key=lambda e: e[0]):
            if kind == "put":
                self.oracle.put(p[0], p[1])
            elif kind == "delete":
                self.oracle.delete(p[0])
            else:
                keys, found, vals = p
                want_f, ver = self.oracle.expect(keys)
                want_v = self.make_values(keys, ver)
                compared += keys.shape[0]
                bad = wrong_keys(want_f, want_v, found, vals)
                if bad:
                    self._note_wrong(keys, want_f, ver, want_v, found, vals)
                wrong += bad
                wrong_reads += bad > 0
                if after_seq is not None and seq >= after_seq:
                    wrong_after += bad
        self.events.clear()
        return compared, wrong, wrong_reads, wrong_after

    def _note_wrong(self, keys, want_f, ver, want_v, found, vals) -> None:
        bad = (found != want_f) | (want_f & np.any(vals != want_v, axis=1))
        for i in np.nonzero(bad)[0]:
            self.wrong_detail.append((
                int(keys[i]), bool(want_f[i]), int(ver[i]), bool(found[i]),
                bytes(want_v[i, :8]).hex(), bytes(vals[i, :8]).hex()))
