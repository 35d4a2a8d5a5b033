"""A mix of other traffic files run together: ``parts`` names them, and
each part keeps its own generator. The lead-in is the longest part's, so
every part is in steady state when the window opens."""

from __future__ import annotations

from typing import Any, Dict


def build(traffic: Dict[str, Any], env) -> "Compose":
    return Compose(traffic, env)


class Compose:
    def __init__(self, traffic: Dict[str, Any], env):
        self.parts = [
            env.build_generator(env.load_traffic(p["traffic"]))
            for p in traffic["parts"]
        ]
        self.lead_in_s = max(p.lead_in_s for p in self.parts)
        self.drain_s = max(p.drain_s for p in self.parts)

    def warm(self) -> None:
        for p in self.parts:
            p.warm()

    def start(self, t0: float) -> None:
        for p in self.parts:
            p.start(t0)

    def stop(self, t_end: float) -> None:
        # the open loop first: its stragglers need the batch still running
        for p in reversed(self.parts):
            p.stop(t_end)
