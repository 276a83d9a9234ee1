"""What one run observed, handed to every metric reader.

A reader (``bench/metrics/<name>.py``) defines ``read(ctx)`` and returns a
number, or ``None`` where the run has nothing for it to read.  Times are
host ``time.perf_counter()`` seconds unless said otherwise.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Move:
    t_cmd: float                # evict command sent
    t_resumed: float            # resume (or migrate-in) returned
    replica: str
    src: str
    dst: str
    t_first: float = None       # first token that replica delivered after


@dataclass
class RunContext:
    cfg: dict                   # bench/configs/<config>.json
    mix: dict                   # bench/traffic/<traffic>.json
    peaks: dict                 # bench/peaks.json entry of the device
    chips: int
    t0: float                   # window open
    t1: float                   # window close
    setup_s: float
    records: list = field(default_factory=list)   # clients.Record
    moves: list = field(default_factory=list)     # Move
    evicts: list = field(default_factory=list)    # eviction stats in window
    split: dict = None          # host_device_split() window deltas
    trace: dict = None          # trace.reduce() of the traced window

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def traced_t1(self) -> float:
        """Host time at which the traced window ended (the trace reduction
        cuts it where the device record stops)."""
        return self.t0 + self.trace["window_s"]

    def in_window(self, t: float, t1: float = None) -> bool:
        return self.t0 <= t <= (self.t1 if t1 is None else t1)

    def window_tokens(self, t1: float = None) -> list:
        """(record, token index) of every token delivered in the window (or
        up to ``t1``), requests still in flight at its close included."""
        return [(r, j) for r in self.records if r.index >= 0
                for j, t in enumerate(r.times) if self.in_window(t, t1)]

    def gaps(self) -> np.ndarray:
        """Every gap between consecutive tokens of a request whose later
        token was delivered in the window."""
        return np.asarray([r.times[j] - r.times[j - 1]
                           for r, j in self.window_tokens() if j > 0])

    def decode_contexts(self, t1: float = None) -> list:
        """Context (positions attended, the new one included) of the decode
        step that produced each token after the first, in the window."""
        return [r.bucket + j for r, j in self.window_tokens(t1) if j > 0]

    def admissions(self, t1: float = None) -> list:
        """Records whose prefill delivered its first token in the window."""
        return [r for r in self.records if r.index >= 0 and r.times
                and self.in_window(r.times[0], t1)]
