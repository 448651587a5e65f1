"""Backend compiles counted by phase: a copy of ``chip_smoke.py``'s
``jax.monitoring`` listener, kept with the benchmark."""
from __future__ import annotations

from collections import defaultdict

import jax


class CompileLog:
    """Backend compile seconds per program name, attributed to the open
    phase (a ``jax.monitoring`` listener). A program loaded from the
    persistent cache is not a backend compile and is not counted."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.phase = "set-up"
        self.events: list = []          # (phase, program, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.events.append((self.phase, str(kw.get("fun_name", "?")),
                                float(duration)))

    def count(self, phase: str) -> int:
        return sum(1 for p, _, _ in self.events if p == phase)

    def seconds(self, phase: str) -> float:
        return sum(d for p, _, d in self.events if p == phase)

    def by_program(self, phase: str) -> dict:
        out: dict = defaultdict(float)
        for p, name, d in self.events:
            if p == phase:
                out[name] += d
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))
