"""What one run measured, as the metric reducers read it."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from bench.serve_loop import Window
from bench.trace import Summary


@dataclass
class RunRecord:
    cell: dict                    # the BENCHMARK.json workload entry
    config: dict                  # the configuration file
    traffic: dict                 # the traffic mix
    setup_s: float
    window: Window
    readings: dict                # from check.compare
    device_kind: str
    shape: dict = field(default_factory=dict)   # rows, dim, degree served
    trace: Optional[Summary] = None
