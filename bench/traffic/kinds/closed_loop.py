"""Closed loop: ``clients`` callers, each with one request outstanding,
sending the next as soon as the last is answered (callers that wait for
each reply).  Each request is timed from its submission."""

from __future__ import annotations


class ClosedLoop:
    def __init__(self, clients: int, make_request) -> None:
        if clients < 1:
            raise ValueError("clients must be >= 1")
        self.clients = clients
        self.make_request = make_request
        self.free = 0

    def start(self, now: float) -> None:
        self.free = self.clients

    def poll(self, now: float) -> list:
        out = [self.make_request(now) for _ in range(self.free)]
        self.free = 0
        return out

    def next_due(self):
        return None               # nothing is scheduled: answers free callers

    def completed(self, req, now: float) -> None:
        self.free += 1


def make(mix: dict, rng, make_request) -> ClosedLoop:
    return ClosedLoop(int(mix["clients"]), make_request)
