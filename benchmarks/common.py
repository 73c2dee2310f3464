"""Shared benchmark scaffolding: CSV emission."""
from __future__ import annotations

from typing import List, Tuple

ROWS: List[Tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}")

