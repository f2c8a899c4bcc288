"""Straggler detection & mitigation — and the serving decode-step watchdog.

Per-step wall times feed an EWMA; a host whose step exceeds
`threshold x EWMA` is flagged.  Mitigation is pluggable: the trainer installs
a callback that (a) logs, (b) reassigns the straggler's data shards to healthy
hosts via `DataReassigner` (the synthetic pipeline is keyed by (host, shard)
so reassignment is just arithmetic), and (c) after `evict_after` consecutive
flags, requests an elastic re-mesh (runtime/elastic.py).

`DecodeStepWatchdog` promotes the same EWMA machinery into the serving
engine's step loop (serving/engine.py wires it into Engine.stats): per-step
latency EWMA and stall detection (a step slower than `threshold x EWMA`
after warmup).  It keeps no clock: the engine feeds it each step's
`engine.step` span (serving/tracing.py), and its p50/p99 are taken over the
durations the caller passes to `summary` (the tracer's recent step
records).  A stalled decode stream is the first symptom of every fault
class the chaos harness injects (pool livelock, quarantine recompile storms,
clock skew), so the watchdog is the observable the degradation ladder is
judged by (docs/ROBUSTNESS.md).

StepWatchdog's clock is injectable so tests drive it deterministically.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np


@dataclasses.dataclass
class WatchdogConfig:
    ewma_alpha: float = 0.2
    threshold: float = 2.5
    warmup_steps: int = 5
    evict_after: int = 3


class StepWatchdog:
    def __init__(self, cfg: WatchdogConfig = WatchdogConfig(), *, clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.clock = clock
        self.ewma: float | None = None
        self.steps = 0
        self._start: float | None = None
        self.flags: dict[int, int] = {}       # host -> consecutive flags
        self.evicted: set[int] = set()

    def step_start(self):
        self._start = self.clock()

    def step_end(self, *, host_times: dict[int, float] | None = None) -> list[int]:
        """Returns hosts flagged this step.  host_times: per-host durations
        (from an all-gather of step times in a real deployment; injected in
        tests).  Without per-host times, only the global EWMA updates."""
        assert self._start is not None
        dur = self.clock() - self._start
        self._start = None
        self.steps += 1
        if self.ewma is None:
            self.ewma = dur
        else:
            a = self.cfg.ewma_alpha
            self.ewma = a * dur + (1 - a) * self.ewma

        flagged = []
        if host_times and self.steps > self.cfg.warmup_steps:
            for host, t in host_times.items():
                if host in self.evicted:
                    continue
                if t > self.cfg.threshold * self.ewma:
                    self.flags[host] = self.flags.get(host, 0) + 1
                    flagged.append(host)
                    if self.flags[host] >= self.cfg.evict_after:
                        self.evicted.add(host)
                else:
                    self.flags[host] = 0
        return flagged

    def should_remesh(self) -> bool:
        return bool(self.evicted)


class DecodeStepWatchdog:
    """Serving-side step watchdog: EWMA + stall flags + latency percentiles.

    One instance per Engine.  `observe(seconds)` takes each engine step's
    duration, the engine's `engine.step` span (also for a step that
    raised); `summary(durations)` is merged into Engine.stats["watchdog"],
    its percentiles taken over `durations`.
    """

    def __init__(self, cfg: WatchdogConfig = WatchdogConfig()):
        self.cfg = cfg
        self.ewma: float | None = None
        self.steps = 0
        self.stalls = 0
        self.last_stalled = False
        self.last_duration: float = 0.0

    def observe(self, seconds: float) -> bool:
        """Record one step of `seconds`; returns True when it counts as a
        stall (post-warmup step slower than threshold x the running EWMA)."""
        dur = max(float(seconds), 0.0)
        self.steps += 1
        self.last_duration = dur
        stalled = (
            self.steps > self.cfg.warmup_steps
            and self.ewma is not None
            and dur > self.cfg.threshold * self.ewma
        )
        if stalled:
            self.stalls += 1
            # A stall is an outlier by definition: folding it into the EWMA
            # at full weight would teach the watchdog that stalls are normal.
            # Clamp the sample to the flag threshold before updating.
            dur = self.cfg.threshold * self.ewma
        self.last_stalled = bool(stalled)
        if self.ewma is None:
            self.ewma = dur
        else:
            a = self.cfg.ewma_alpha
            self.ewma = a * dur + (1 - a) * self.ewma
        return bool(stalled)

    def summary(self, durations) -> dict:
        """EWMA, stall counts, and p50/p99 over step `durations` (seconds)."""
        d = np.asarray(durations, np.float64)
        return {
            "steps": self.steps,
            "ewma_ms": 1e3 * (self.ewma or 0.0),
            "last_ms": 1e3 * self.last_duration,
            "p50_ms": 1e3 * float(np.percentile(d, 50)) if d.size else 0.0,
            "p99_ms": 1e3 * float(np.percentile(d, 99)) if d.size else 0.0,
            "stalls": self.stalls,
            "stalled": self.last_stalled,
        }


class DataReassigner:
    """Maps logical data shards to surviving hosts after eviction."""

    def __init__(self, num_hosts: int):
        self.num_hosts = num_hosts
        self.assignment = {h: [h] for h in range(num_hosts)}  # host -> shards

    def evict(self, host: int):
        if host not in self.assignment:
            return
        orphaned = self.assignment.pop(host)
        survivors = sorted(self.assignment)
        for i, shard in enumerate(orphaned):
            target = survivors[i % len(survivors)]
            self.assignment[target].append(shard)

    def shards_for(self, host: int) -> list[int]:
        return sorted(self.assignment.get(host, []))
