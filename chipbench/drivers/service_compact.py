"""The decide service with the trigger-gated compacted decide
(``make_decide_jax(..., compact=True)``, DESIGN.md §18): lanes whose
inputs are bitwise unchanged replay their cached row."""

from __future__ import annotations

from chipbench.drivers import service


class Cell(service.Cell):
    compact = True
