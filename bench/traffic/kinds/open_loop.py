"""Open loop: requests arrive as a Poisson process at ``rate_per_s``,
whether or not earlier ones were answered (independent users).  Each
request is timed from its due time."""

from __future__ import annotations


class OpenLoop:
    def __init__(self, rate_per_s: float, rng, make_request) -> None:
        if rate_per_s <= 0:
            raise ValueError("rate_per_s must be > 0")
        self.mean_gap = 1.0 / rate_per_s
        self.rng = rng
        self.make_request = make_request
        self._gaps = iter(())
        self.next = None

    def _gap(self) -> float:
        try:
            return next(self._gaps)
        except StopIteration:
            self._gaps = iter(self.rng.exponential(self.mean_gap,
                                                   8192).tolist())
            return next(self._gaps)

    def start(self, now: float) -> None:
        self.next = now + self._gap()

    def poll(self, now: float) -> list:
        out = []
        while self.next <= now:
            out.append(self.make_request(self.next))
            self.next += self._gap()
        return out

    def next_due(self) -> float:
        return self.next

    def completed(self, req, now: float) -> None:
        pass


def make(mix: dict, rng, make_request) -> OpenLoop:
    return OpenLoop(float(mix["rate_per_s"]), rng, make_request)
