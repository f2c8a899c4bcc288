"""One run of one benchmark cell, driven by the cell's files.

A cell (`workloads` entry of BENCHMARK.json) names a configuration file and
a traffic file; the harness finds them, and each metric's reader, by name:

    <bench>/configs/<config>.json   model, deployment, engine settings
    <bench>/traffic/<traffic>.json  loop, clients or arrivals, lengths
    <bench>/metrics/<metric>.py     LAYER, UNIT, MOVES, read(run)
    <bench>/limits/<workload>.json  the limit of each number `correct` compares
    <bench>/refs/<arch>.py          seeded plain weights, float32 reference
    <bench>/loaders/<arch>.py       the configuration loaded into the program

The run goes through the program's normal serving path only:
`Engine(params, cfg, enc, config=EngineConfig(...))`, `submit` and `step`.
Tokens are timed by `stream_cb`, which fires on the host after the step's
sync, so each timestamp is a completed token.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

import numpy as np

import traffic as traffic_lib

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# Host work that only a program's first call pays: tracing, lowering, and
# compiling or loading it from the persistent cache (the backend-compile
# event spans both).
FIRST_CALL = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    BACKEND_COMPILE,
)
# Warm the shapes of this much more than the window: the window's decode-only
# steps spread by about 10% from p10 to p90 (qwen2-1.5b.decode, one v5e).
WARM_MARGIN = 1.1
FULL_BATCH_STEPS = 3  # steps of the warm full batch; the last is timed
MAX_SIM_STEPS = 200_000


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    bench_dir: str
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    limits: dict

    @property
    def engine_settings(self) -> dict:
        return {**self.config["engine"], **self.traffic.get("engine", {})}

    def metric_names(self, trace: bool) -> list[str]:
        group = self.bench["per_layer"] if trace else self.bench["end_to_end"]
        return [m["name"] for m in group
                if "workloads" not in m or self.name in m["workloads"]]

    def metric(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics", name + ".py"),
                           "metric_" + name.replace(".", "_").replace("-", "_"))

    def ref(self):
        return importlib.import_module("refs." + self.config["arch"])

    def loader(self):
        return importlib.import_module("loaders." + self.config["arch"])


def load_cell(root: str, name: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    byname = {w["name"]: w for w in bench["workloads"]}
    if name not in byname:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {sorted(byname)})")
    wl = byname[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    return Cell(
        name=name, root=root, bench_dir=bench_dir, bench=bench, workload=wl,
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(bench_dir, "traffic", wl["traffic"] + ".json")),
        limits=load_json(os.path.join(bench_dir, "limits", name + ".json")),
    )


class CompileClock:
    """First-call work (FIRST_CALL events), process-wide: (event, function,
    seconds, when it finished)."""

    def __init__(self):
        import jax

        self.events: list[tuple[str, str, float, float]] = []
        self.cache = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_count(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1

    def _on(self, event, duration, **kw):
        if event in FIRST_CALL:
            self.events.append((event, str(kw.get("fun_name")), duration, time.perf_counter()))

    def seconds(self, t0: float, t1: float = float("inf"), kind: str | None = None) -> float:
        return sum(e[2] for e in self.events if t0 <= e[3] <= t1 and kind in (None, e[0]))

    def top(self, t0: float, t1: float, n: int = 12) -> list[tuple[str, int, float]]:
        """The functions that took most backend compile time in [t0, t1]:
        (name, compiles, seconds)."""
        agg: dict[str, list] = {}
        for f, d in self.compiles(t0, t1):
            a = agg.setdefault(f, [0, 0.0])
            a[0] += 1
            a[1] += d
        return sorted(((f, c, round(d, 3)) for f, (c, d) in agg.items()), key=lambda x: -x[2])[:n]

    def compiles(self, t0: float, t1: float = float("inf")) -> list[tuple[str, float]]:
        return [(e[1], e[2]) for e in self.events
                if t0 <= e[3] <= t1 and e[0] == BACKEND_COMPILE]


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    admitted: int = 0
    prompt_tokens: int = 0
    emitted: int = 0
    pages_in_use: int | None = None
    pages_total: int | None = None
    bytes_in_use: int | None = None   # device memory held after the step


@dataclasses.dataclass
class Call:
    phase: str        # "prefill" | "decode"
    step: int         # index into Run.steps
    traced: bool
    rows: int = 0     # decode: active slots; prefill: real prompt tokens
    seqs: int = 0     # prefill: sequences in the batch


@dataclasses.dataclass
class Run:
    """Everything one run measured; the metric readers' only input."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    setup_s: float = 0.0
    window_t0: float = 0.0
    window_t1: float = 0.0
    steps: list[Step] = dataclasses.field(default_factory=list)
    requests: list[traffic_lib.Req] = dataclasses.field(default_factory=list)
    calls: list[Call] = dataclasses.field(default_factory=list)
    window_compiles: list = dataclasses.field(default_factory=list)
    window_first_call_s: float = 0.0
    setup_compile_s: float = 0.0
    warm: dict = dataclasses.field(default_factory=dict)
    summary: object = None        # trace_reduce.TraceSummary of the traced window
    device: dict = dataclasses.field(default_factory=dict)
    slots: int = 0
    degraded: int = 0

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def in_window(self, t: float | None) -> bool:
        return t is not None and self.window_t0 <= t <= self.window_t1

    @property
    def cfg(self) -> dict:
        return self.cell.config


class Sim:
    """The engine's phase-split schedule for a traffic mix, replayed on the
    host without the model: which requests each step admits (batch, padded
    length, prompt blocks) and the decode block-table width it runs at.

    Admission admits every queued request into a free slot and prefills
    them as one batch padded to a power of two (at least one block, at most
    the table); decode then gives every active slot one token; a request
    ends at its drawn output length.  With no EOS, greedy tokens and a pool
    that covers every slot's worst case, this is exactly the engine's
    schedule for a closed loop; an open loop's is approximated with the
    step times measured while warming up."""

    def __init__(self, traffic: dict, mix: traffic_lib.Mix, slots: int, max_seq: int,
                 block: int):
        self.gen = traffic_lib.generator(traffic, mix)
        self.slots, self.block = slots, block
        self.num_blocks = -(-max_seq // block)
        self.queue: list[traffic_lib.Req] = []
        self.active: list[list] = []   # [req, tokens generated]
        self.gen.start(0.0)

    def width(self, pages: int) -> int:
        if self.num_blocks <= 8:
            return self.num_blocks
        return min(self.num_blocks, 1 << (max(pages, 1) - 1).bit_length())

    def padded(self, length: int) -> int:
        return max(self.block, min(1 << (length - 1).bit_length(), self.num_blocks * self.block))

    def admit(self, now: float):
        """Admit what is due; returns (admission or None, decode width)."""
        self.queue += self.gen.due(now)
        free = self.slots - len(self.active)
        adm, self.queue = self.queue[:free], self.queue[free:]
        combo = None
        if adm:
            lens = tuple(r.prompt_len for r in adm)
            combo = (len(adm), self.padded(max(lens)),
                     sum(max(1, -(-n // self.block)) for n in lens), lens)
            self.active += [[r, 0] for r in adm]
        if not self.active:
            return combo, None
        pages = max((r.prompt_len + g - 1) // self.block + 1 for r, g in self.active)
        return combo, self.width(pages)

    def finish(self, now: float) -> None:
        """Every active slot emits one token at `now`."""
        keep = []
        for slot in self.active:
            slot[1] += 1
            if slot[1] >= slot[0].out_len:
                self.gen.complete(slot[0], now)
            else:
                keep.append(slot)
        self.active = keep


class Harness:
    """Builds the cell's engine, warms it, and runs its window."""

    def __init__(self, cell: Cell, seed: int, seconds: float, *, trace: bool = False,
                 clock: CompileClock | None = None, t_start: float | None = None):
        import jax

        from repro.core.packed import EncodingConfig
        from repro.serving import engine as engine_lib
        from repro.serving.config import EngineConfig

        self.jax = jax
        self.engine_lib = engine_lib
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace = trace
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.clock = clock or CompileClock()
        self.run = Run(cell=cell, seed=self.seed, seconds=self.seconds, trace=trace)
        settings = cell.engine_settings
        self.loader = cell.loader()
        self.mcfg = self.loader.model_config(cell.config)
        self.enc = EncodingConfig(enabled=True, **cell.config["encoding"])
        self.params = self.loader.program_params(cell.config, self.mcfg, self.enc, self.seed)
        jax.block_until_ready(self.params)
        self.eng = engine_lib.Engine(self.params, self.mcfg, self.enc,
                                     config=EngineConfig(**settings),
                                     stream_cb=self._on_token)
        self.run.slots = self.eng.slots
        self.settings = settings
        self.mix = traffic_lib.Mix(cell.traffic, self.seed, cell.config["vocab_size"])
        self.gen = traffic_lib.generator(cell.traffic, self.mix)
        self.inflight: dict[int, traffic_lib.Req] = {}
        self.warm_uid = -1
        self.tracing = False
        self.step_emitted = 0
        self._wrap()

    # ---- instrumentation around the program's calls ------------------------

    def _wrap(self) -> None:
        jax = self.jax
        eng = self.eng
        prefill, decode = eng.prefill_fn, eng.decode_fn

        def prefill_wrapped(*args, **kw):
            with jax.profiler.TraceAnnotation("bench.prefill"):
                out = prefill(*args, **kw)
            self.run.calls.append(Call("prefill", len(self.run.steps), self.tracing))
            return out

        def decode_wrapped(*args, **kw):
            rows = sum(r is not None for r in eng.slot_req)
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = decode(*args, **kw)
            self.run.calls.append(Call("decode", len(self.run.steps), self.tracing, rows=rows))
            return out

        eng.prefill_fn, eng.decode_fn = prefill_wrapped, decode_wrapped

    def _on_token(self, req, token: int) -> None:
        self.step_emitted += 1
        rec = self.inflight.get(req.uid)
        if rec is not None:
            rec.token_times.append(time.perf_counter())
            rec.tokens.append(int(token))

    def _submit(self, rec: traffic_lib.Req, now: float) -> None:
        rec.submit = now
        prompt = self.mix.prompt(rec.uid, rec.prompt_len)
        ok = self.eng.submit(self.engine_lib.Request(
            uid=rec.uid, prompt=prompt, max_new_tokens=rec.out_len))
        self.run.requests.append(rec)
        if ok:
            rec.status = "running"
            self.inflight[rec.uid] = rec
        else:
            rec.status = "rejected"

    def _step(self, record: bool) -> Step:
        """One engine step: submit what is due, step, account for it."""
        now = time.perf_counter()
        for rec in self.gen.due(now):
            self._submit(rec, now)
        self.step_emitted = 0
        st = Step(t0=time.perf_counter(), t1=0.0)
        with self.jax.profiler.StepTraceAnnotation("bench.step", step_num=len(self.run.steps)):
            self.eng.step()
        st.t1 = time.perf_counter()
        st.emitted = self.step_emitted
        idx = len(self.run.steps)
        for rec in list(self.inflight.values()):
            if rec.admit_step is None and rec.token_times:
                rec.admit_step = idx if record else -1
                st.admitted += 1
                st.prompt_tokens += rec.prompt_len
            if rec.done:
                rec.status = "ok"
                del self.inflight[rec.uid]
                self.gen.complete(rec, rec.token_times[-1])
        if record:
            if self.trace:
                stats = self.eng.stats
                st.pages_in_use, st.pages_total = stats.get("pages_in_use"), stats.get("pages_total")
                st.bytes_in_use = max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                                       for d in self.jax.local_devices()), default=None)
            for call in self.run.calls:
                if call.step == idx and call.phase == "prefill":
                    call.rows, call.seqs = st.prompt_tokens, st.admitted
            self.run.steps.append(st)
        else:
            self.run.calls = [c for c in self.run.calls if c.step != idx]
        return st

    # ---- warm-up -----------------------------------------------------------

    def _warm_step(self, lengths, max_new: int) -> float:
        """Admit warm-up requests of `lengths` (prompts no traffic request
        shares), step until they end; returns the seconds of the last step
        less the compiles in it."""
        reqs = []
        for n in lengths:
            rng = np.random.default_rng([self.seed, 4, -self.warm_uid])
            prompt = rng.integers(1, self.cell.config["vocab_size"], n).astype(np.int32)
            req = self.engine_lib.Request(uid=self.warm_uid, prompt=prompt,
                                          max_new_tokens=max_new)
            self.warm_uid -= 1
            if not self.eng.submit(req):
                raise RuntimeError(f"warm-up request of {n} tokens refused")
            reqs.append(req)
        last = 0.0
        while not all(r.done for r in reqs):
            t0 = time.perf_counter()
            with self.jax.profiler.StepTraceAnnotation("bench.warm"):
                self.eng.step()
            t1 = time.perf_counter()
            last = t1 - t0 - self.clock.seconds(t0, t1)
        self.run.calls.clear()
        return max(last, 0.0)

    def _warm_width(self, sim: Sim, width: int) -> float:
        """Decode at block-table `width`: one request of a length the
        traffic sends, decoding until its pages need that width, and one
        step more, which is timed."""
        length = min(width * sim.block - 1, int(self.cell.traffic["prompt"]["max"]))
        grown = 0
        while sim.width((length + grown - 1) // sim.block + 1) < width:
            grown += 1
        return self._warm_step([length], grown + 2)

    def warm_up(self) -> None:
        """Run every program the window will run, before it opens: each
        admission (batch, padded length, prompt blocks) and each decode
        table width of the replayed schedule, for WARM_MARGIN x the window.

        A closed loop's schedule is timed with a full batch: the set-up's
        own admission of every client runs first, on warm-up prompts of the
        same lengths, and its last decode step is timed; an admitting step
        takes its prefill's time more.  An open loop's is timed with the
        warm-up's own steps, one admission at a time."""
        s = self.settings
        sim = Sim(self.cell.traffic, traffic_lib.Mix(self.cell.traffic, self.seed,
                                                     self.cell.config["vocab_size"]),
                  self.eng.slots, s["max_seq"], s.get("block_size", 16))
        closed = self.cell.traffic["loop"] == "closed"
        decode_s: dict[int, float] = {}
        admit_s: dict[tuple, float] = {}
        full_s = None
        t, steps = 0.0, 0
        budget = self.seconds * WARM_MARGIN
        while steps < MAX_SIM_STEPS:
            combo, width = sim.admit(t)
            first = closed and steps == 0  # the set-up's own first step
            if width is not None and width not in decode_s:
                decode_s[width] = self._warm_width(sim, width)
            if first:
                full_s = self._warm_step(list(combo[3]), FULL_BATCH_STEPS)
                dt = 0.0
            elif combo is not None:
                key = combo[:3]
                if key not in admit_s:
                    admit_s[key] = self._warm_step(list(combo[3]), 1)
                dt = admit_s[key] + (full_s or 0.0)
            elif width is not None:
                dt = decode_s[width] if full_s is None else full_s
            else:
                dt = 1e-3  # nothing to do: the open loop waits for arrivals
            steps += 1
            t += dt
            sim.finish(t)
            if not first and t >= budget:
                break
        self.run.warm = {"admissions": len(admit_s), "widths": sorted(decode_s),
                         "steps": steps, "est_s": t, "full_batch_step_s": full_s}

    # ---- the run -----------------------------------------------------------

    def _start_trace(self, tdir: str) -> None:
        jax = self.jax
        jax.block_until_ready(self.eng.caches)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        self.tracing = True

    def _stop_trace(self, tdir: str) -> None:
        """Stop the trace and reduce it.  On a TPU a trace with no device
        plane is an error: the run then reports nothing."""
        import trace_reduce

        self.jax.block_until_ready(self.eng.caches)
        self.jax.profiler.stop_trace()
        self.tracing = False
        found = [os.path.join(r, f) for r, _, fs in os.walk(tdir) for f in fs
                 if f.endswith(".xplane.pb")]
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file under {tdir}, found {found}")
        self.run.summary = trace_reduce.reduce_trace(found[0])
        if not self.run.summary.devices and self.jax.devices()[0].platform == "tpu":
            raise RuntimeError("the trace holds no device plane with an 'XLA Ops' line; "
                               f"planes and their lines: {self.run.summary.planes}")

    def execute(self) -> Run:
        """Warm up, set up the load, measure for `seconds`, trace if asked."""
        jax = self.jax
        run = self.run
        self.warm_up()
        if self.cell.traffic["loop"] == "closed":
            # Every client's first request is admitted in set-up, in one batch.
            self.gen.start(time.perf_counter())
            self._step(record=False)
        jax.block_until_ready(self.eng.caches)
        run.setup_compile_s = self.clock.seconds(0.0, kind=BACKEND_COMPILE)
        run.window_t0 = time.perf_counter()
        run.setup_s = run.window_t0 - self.t_start
        if self.cell.traffic["loop"] != "closed":
            self.gen.start(run.window_t0)
        t_end = run.window_t0 + self.seconds
        # The trace covers the window's last `trace_seconds`, and stops after
        # the window has closed, so that writing it takes nothing from it.
        trace_s = min(float(self.cell.traffic.get("trace_seconds", 4.0)), self.seconds)
        t_trace = t_end - trace_s
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if self.trace else None
        try:
            while time.perf_counter() < t_end:
                if self.trace and not self.tracing and time.perf_counter() >= t_trace:
                    self._start_trace(tdir)
                self._step(record=True)
            run.window_t1 = run.steps[-1].t1 if run.steps else time.perf_counter()
            if self.tracing:
                self._stop_trace(tdir)
        finally:
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False
            if tdir:
                shutil.rmtree(tdir, ignore_errors=True)
        jax.block_until_ready(self.eng.caches)
        run.window_compiles = self.clock.compiles(run.window_t0, run.window_t1)
        run.window_first_call_s = self.clock.seconds(run.window_t0, run.window_t1)
        dev = jax.devices()
        run.device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
                      "count": len(dev),
                      "memory_peak_bytes": max(int((d.memory_stats() or {}).get(
                          "peak_bytes_in_use", 0)) for d in dev)}
        run.degraded = len(self.eng.degraded)
        return run

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.eng = None
        self.params = None
        gc.collect()
        self.jax.clear_caches()
        gc.collect()


# ---- correctness ------------------------------------------------------------


def sample_for_check(run: Run) -> list[traffic_lib.Req]:
    """Requests finished in the window: the one with the longest sequence,
    then others in an order drawn from the seed, until the traffic's
    `check.tokens` served tokens or `check.max_requests`."""
    check = run.cell.traffic.get("check", {})
    want, most = int(check.get("tokens", 256)), int(check.get("max_requests", 8))
    done = [r for r in run.requests if r.done and run.in_window(r.token_times[-1])]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.out_len, r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([run.seed, 6]).permutation(len(rest))
    out, tokens = [longest], longest.out_len
    for i in order:
        if tokens >= want or len(out) >= most:
            break
        out.append(rest[i])
        tokens += rest[i].out_len
    return out


def compare(cell: Cell, seed: int, seqs, degraded: int, *, low: bool = False):
    """The numbers `correct` compares, each with the cell's limit, for
    served sequences `seqs` ((prompt, served tokens) pairs), and the widest
    single gap (reported, not compared).

    `mean_logit_gap` is the mean, over the served tokens, of the gap by
    which the token's float32 reference logit lies below the reference's
    best: 0 where every served token is the reference's argmax.  With
    `low`, the control: the gap of the token that the float8 reference puts
    first."""
    out = {"kernel_faults": {"value": degraded, "limit": 0}}
    if not seqs:
        out["served_tokens_checked"] = {"value": 0, "limit": 1}
        return out, None
    gaps = np.concatenate(cell.ref().logit_gaps(cell.config, seed, seqs, low=low))
    out["mean_logit_gap"] = {"value": float(np.mean(gaps)),
                             "limit": float(cell.limits["mean_logit_gap"])}
    out["served_tokens_checked"] = {"value": int(gaps.size), "limit": 1}
    return out, float(np.max(gaps))


def check(run: Run, mix: traffic_lib.Mix, *, low: bool = False):
    """`compare` on the run's sample of finished requests."""
    seqs = [(mix.prompt(r.uid, r.prompt_len), np.asarray(r.tokens, np.int32))
            for r in sample_for_check(run)]
    return compare(run.cell, run.seed, seqs, run.degraded, low=low)


def is_correct(numbers: dict) -> bool:
    ok = numbers["kernel_faults"]["value"] <= numbers["kernel_faults"]["limit"]
    ok &= numbers["served_tokens_checked"]["value"] >= numbers["served_tokens_checked"]["limit"]
    if "mean_logit_gap" in numbers:
        ok &= numbers["mean_logit_gap"]["value"] <= numbers["mean_logit_gap"]["limit"]
    else:
        ok = False
    return bool(ok)


def read_metrics(run: Run, names: list[str]) -> dict:
    """Each metric's reader on the run; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for name in names:
        mod = run.cell.metric(name)
        value = mod.read(run)
        if value is not None:
            if isinstance(value, float) and not math.isfinite(value):
                raise ValueError(f"metric {name} read {value}")
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out
