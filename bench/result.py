"""What a driver hands back to the harness."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Check:
    """One compared number: the reading and the limit it must not pass."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Result:
    """What a driver hands back."""
    e2e: dict                      # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list                   # [Check]
    stats: dict                    # raw numbers the per-layer readers use
