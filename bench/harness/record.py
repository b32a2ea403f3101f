"""What a run's window did, step by step, on the host clock: the record
that the end-to-end metrics and the per-layer readers read."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    # (n, start, head): n tokens at positions start .. start + n - 1 of
    # one sequence, each through every layer and, with ``head``, through
    # the output head
    segments: list


@dataclasses.dataclass
class Record:
    t_open: float = 0.0
    t_close: float = 0.0
    steps: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def window(self) -> list:
        return [st for st in self.steps if st.t0 >= self.t_open]

    def tokens(self) -> int:
        return sum(n for st in self.window() for n, _, _ in st.segments)
