"""Seeded load generation for one traffic mix (a `traffic/<mix>.json` file).

A mix fixes the length distributions and the loop:

    {"loop": "closed", "clients": 64,
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1024},
     "output": {"dist": "uniform", "min": 8, "max": 32},
     "block": 64}

    {"loop": "open", "arrival": {"kind": "poisson", "rate": 4.0}, ...}
    {"loop": "open", "arrival": {"kind": "onoff", "rate": 4.0, "burst_factor": 4,
                                 "on_s": 2, "period_s": 10}, ...}

Every seed gets the same sizes and gaps in the same layout, so that every
seed asks the program for the same shapes: lengths are the distribution's
quantiles at (i + 0.5) / block, and each run of `block` consecutive
requests holds each quantile once, in an order fixed by the layout (not by
the seed).  Arrival gaps are laid out the same way from the unit
exponential.  The seed draws the prompt tokens and, in a closed loop, which
client runs which lane of the layout.

A closed loop gives each client one request in flight: its next request is
due as soon as the previous one completed.  An open loop sends on its
schedule whatever has completed.  Requests are timed from their due time;
the harness records how late each was submitted.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

LAYOUT = 0  # the order of sizes and gaps, the same for every seed


def quantiles(spec: dict, n: int) -> np.ndarray:
    """`n` lengths at the quantiles (i + 0.5) / n of `spec`, clipped to
    [min, max] and rounded to whole tokens."""
    lo, hi = int(spec["min"]), int(spec["max"])
    p = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in p])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + (hi - lo) * p
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


@dataclasses.dataclass
class Req:
    """One request as the benchmark sees it; times are host seconds."""

    uid: int
    prompt_len: int
    out_len: int
    due: float
    client: int | None = None
    submit: float | None = None
    admit_step: int | None = None
    token_times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    status: str = "pending"

    @property
    def first(self) -> float | None:
        return self.token_times[0] if self.token_times else None

    @property
    def done(self) -> bool:
        return len(self.token_times) >= self.out_len


class Mix:
    """Sizes and prompt tokens of request i, for one mix and seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.block = int(traffic.get("block", 64))
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.prompt_q = quantiles(traffic["prompt"], self.block)
        self.out_q = quantiles(traffic["output"], self.block)
        self._perms: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def _perm(self, b: int) -> tuple[np.ndarray, np.ndarray]:
        if b not in self._perms:
            rng = np.random.default_rng([LAYOUT, 1, b])
            self._perms[b] = (rng.permutation(self.block), rng.permutation(self.block))
        return self._perms[b]

    def sizes(self, i: int) -> tuple[int, int]:
        pp, po = self._perm(i // self.block)
        j = i % self.block
        return int(self.prompt_q[pp[j]]), int(self.out_q[po[j]])

    def prompt(self, i: int, length: int | None = None) -> np.ndarray:
        length = self.sizes(i)[0] if length is None else length
        rng = np.random.default_rng([self.seed, 2, i])
        return rng.integers(1, self.vocab, size=length, dtype=np.int64).astype(np.int32)

    def request(self, uid: int, due: float, client: int | None = None,
                index: int | None = None) -> Req:
        """Request `uid`, with the sizes of layout entry `index` (else `uid`)."""
        p, o = self.sizes(uid if index is None else index)
        return Req(uid=uid, prompt_len=p, out_len=o, due=due, client=client)


class ClosedLoop:
    """`clients` callers, each sending its next request as soon as its
    previous one completed.

    Lane l of the layout is the sequence of requests l, l + clients,
    l + 2 x clients, ...; the seed draws which client runs which lane."""

    def __init__(self, mix: Mix, clients: int):
        self.mix, self.clients = mix, int(clients)
        self.lane = np.random.default_rng([mix.seed, 5]).permutation(self.clients)
        self.sent = [0] * self.clients
        self.next_uid = 0
        self.pending: list[Req] = []

    def _new(self, client: int, due: float) -> Req:
        lane = int(self.lane[client])
        r = self.mix.request(self.next_uid, due, client,
                             index=self.sent[client] * self.clients + lane)
        self.next_uid += 1
        self.sent[client] += 1
        self.pending.append(r)
        return r

    def start(self, t0: float) -> None:
        """Every client sends its first request at t0.  The first request of
        lane l asks for (l + 0.5) / clients of its output, so that
        completions are spread as in a loop that has run for a while, and
        not all due at once."""
        for c in range(self.clients):
            r = self._new(c, t0)
            share = (int(self.lane[c]) + 0.5) / self.clients
            r.out_len = max(1, math.ceil(r.out_len * share))

    def due(self, now: float) -> list[Req]:
        out = [r for r in self.pending if r.due <= now]
        self.pending = [r for r in self.pending if r.due > now]
        return out

    def complete(self, req: Req, now: float) -> None:
        self._new(req.client, now)


class OpenLoop:
    """Arrivals on a seeded schedule, sent whatever has completed.

    `poisson`: unit-exponential gaps over `rate`.  `onoff`: the same gaps
    laid on a rate of `burst_factor` x `rate` for `on_s` of every
    `period_s` seconds, and the rate that keeps the mean at `rate` for the
    rest of the period."""

    def __init__(self, mix: Mix, arrival: dict):
        self.mix = mix
        self.arrival = dict(arrival)
        kind = self.arrival["kind"]
        if kind not in ("poisson", "onoff"):
            raise ValueError(f"unknown arrival kind {kind!r}")
        rate = float(self.arrival["rate"])
        if kind == "onoff":
            f, on, per = (float(self.arrival[k]) for k in ("burst_factor", "on_s", "period_s"))
            off_rate = rate * (per - f * on) / (per - on)
            if off_rate < 0:
                raise ValueError("burst_factor x on_s exceeds the period's mean load")
            self.rates = (rate * f, off_rate, on, per)
        else:
            self.rates = (rate, rate, 0.0, 1.0)
        q = (np.arange(mix.block) + 0.5) / mix.block
        self.unit_gaps = -np.log1p(-q)
        self.t0 = 0.0
        self.next_uid = 0
        self.next_due = math.inf
        self._work = 0.0  # cumulative intensity of the next arrival

    def _unit_gap(self, i: int) -> float:
        perm = np.random.default_rng([LAYOUT, 3, i // self.mix.block]).permutation(
            self.mix.block
        )
        return float(self.unit_gaps[perm[i % self.mix.block]])

    def _time_of(self, work: float) -> float:
        """Seconds after t0 at which the cumulative intensity reaches `work`."""
        on_rate, off_rate, on, per = self.rates
        per_period = on_rate * on + off_rate * (per - on)
        k = math.floor(work / per_period)
        rest = work - k * per_period
        if rest <= on_rate * on:
            return k * per + rest / on_rate
        return k * per + on + (rest - on_rate * on) / off_rate

    def schedule(self, n: int) -> list[float]:
        """Due times (seconds after the start) of the first `n` requests."""
        work, out = 0.0, []
        for i in range(n):
            work += self._unit_gap(i)
            out.append(self._time_of(work))
        return out

    def start(self, t0: float) -> None:
        self.t0 = t0
        self._work = self._unit_gap(0)
        self.next_due = t0 + self._time_of(self._work)

    def due(self, now: float) -> list[Req]:
        out = []
        while self.next_due <= now:
            out.append(self.mix.request(self.next_uid, self.next_due))
            self.next_uid += 1
            self._work += self._unit_gap(self.next_uid)
            self.next_due = self.t0 + self._time_of(self._work)
        return out

    def complete(self, req: Req, now: float) -> None:
        pass


def generator(traffic: dict, mix: Mix):
    """The load generator a mix names."""
    if traffic["loop"] == "closed":
        return ClosedLoop(mix, traffic["clients"])
    if traffic["loop"] == "open":
        return OpenLoop(mix, traffic["arrival"])
    raise ValueError(f"unknown loop {traffic['loop']!r}")
