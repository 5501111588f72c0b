"""One run of one benchmark cell: set-up, the open-loop window, the
readings and the check against the plain reference.

Everything a cell needs is found by name: the cell file
``bench/cells/<cell>.json`` names its configuration
(``bench/configs/<config>.json``, with the reference module it names under
``bench/reference/``) and its traffic mix (``bench/traffic/<mix>.json``);
``BENCHMARK.json`` says which metrics the cell reports, and each metric is
read by ``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a
mix or a metric adds files; nothing here changes.

The system under test is the served engine (``repro.launch.serve``'s
deployment) driven through ``InferenceEngine.submit`` and ``step``.  Every
time is stamped here on ``time.perf_counter()``; the engine's own
cost-model clock and its TTFT/TPOT histograms are never read.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import itertools
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, loadgen, oracle, peaks, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


# ----------------------------------------------------------------- the spec
def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of ``bench/`` by path (names may hold dots)."""
    name = "bench_file_" + "_".join(path.relative_to(BENCH).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellSpec:
    name: str
    cell: Dict                   # bench/cells/<name>.json
    config: Dict                 # bench/configs/<config>.json
    mix: Dict                    # bench/traffic/<mix>.json
    end_to_end: List[Dict]       # BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]

    @property
    def serve(self) -> Dict:
        return self.config["serve"]

    def reports(self, metric: str) -> bool:
        return any(m["name"] == metric
                   for m in self.end_to_end + self.per_layer)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _merge(base: Dict, over: Optional[Dict]) -> Dict:
    """``base`` with the keys of ``over`` replaced, nested dicts merged."""
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def load_spec(workload: str, root: Path = ROOT,
              overrides: Optional[Dict] = None) -> CellSpec:
    """The cell ``workload`` as its files define it.  ``overrides``
    (``{"config": ..., "mix": ..., "cell": ...}``) replace keys of those
    files; the CPU tests use it to run a cell at a small size."""
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    over = overrides or {}
    cell = _merge(load_json(BENCH / "cells" / f"{workload}.json"),
                  over.get("cell"))
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _merge(load_json(root / cfg_entry["file"]), over.get("config"))
    mix = _merge(load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
                 over.get("mix"))
    return CellSpec(
        name=workload, cell=cell, config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reference_module(config: Dict) -> ModuleType:
    return load_module(BENCH / "reference" / f"{config['reference']}.py")


# ------------------------------------------------------ the system under test
def model_config(config: Dict, ref: ModuleType):
    """The program's ``ModelConfig`` for a configuration file: the
    registry's entry with every field the file sets replaced."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = dataclasses.replace(get_config(config["program_config"]),
                              **ref.program_fields(config))
    why = T.fused_step_blocker(cfg)
    if why:
        raise ValueError(f"{cfg.name} cannot be served: {why}")
    return cfg


def check_param_layout(cfg, params) -> None:
    """The benchmark's weights must have exactly the program's layout."""
    from repro.models import transformer as T
    want = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")


def build_engine(cfg, params, serve: Dict, telemetry: bool):
    from repro.launch.serve import build_engine as program_build_engine
    eng = program_build_engine(cfg, params, max_seq=int(serve["max_seq"]),
                               max_batch=int(serve["max_batch"]),
                               telemetry=telemetry)
    if not eng.use_fused:
        raise ValueError("the engine did not take the fused paged step")
    return eng


def reset_engine(eng) -> None:
    """Drop every request the engine holds, releasing its pages and its
    dispatcher accounting, so the engine is as built (counters aside)."""
    from repro.core.dispatcher import release_request
    for r in list(eng.running) + list(eng.prefilling):
        eng.kv.release(r.rid)
        ar = eng.attn_reqs.pop(r.rid, None)
        if ar is not None:
            release_request(eng.workers, ar)
    eng.running.clear()
    eng.prefilling.clear()
    eng.queue.clear()
    eng.finished.clear()


# the dry run's step times, as multiples of the modelled ones: the window's
# steps run a little faster or slower than the model, and so reach other
# buckets.  The factors after the first run only while a round has spent
# less than DRY_RUN_BUDGET_S: where the engine's host work is heavy (its
# migrations are real device copies, also in the dry run) a round covers
# the modelled timeline alone.  Rounds go on until one meets no new bucket,
# and a third starts only while the dry runs, warm-ups left out, have spent
# less than DRY_RUN_ROUNDS_BUDGET_S.
DRY_RUN_FACTORS = (1.0, 0.8, 1.25)
DRY_RUN_BUDGET_S = 30.0
DRY_RUN_ROUNDS = 3
DRY_RUN_ROUNDS_BUDGET_S = 90.0


class _NoDeviceStep:
    """Stands in for the fused step during the dry run: no device work, the
    pools come back untouched and every row's next token is 0.  The engine
    records each call's bucket in ``_fused_shapes`` itself."""

    def __init__(self, eng):
        self.eng = eng

    def __call__(self, params, kps, vps, *dev):
        rows = len(self.eng.running) + len(self.eng.prefilling)
        return np.zeros((max(rows, 1), 1), np.float32), kps, vps


class _Buckets(set):
    """``eng._fused_shapes`` during the dry run: the set of buckets called,
    which also keeps the bucket of the latest call."""
    last: Optional[Tuple[int, int, int, int]] = None

    def add(self, bucket) -> None:
        self.last = bucket
        super().add(bucket)


class ModelClock:
    """Clock of the dry run: each step takes the host's own seconds for it
    (the device step stubbed out) plus the warm seconds of the bucket it
    called, the sum times ``factor``.  A bucket met for the first time is
    warmed and timed by ``time_call`` before the clock moves on."""

    def __init__(self, buckets: _Buckets, times: Dict, factor: float,
                 time_call: Callable[[Tuple[int, int, int, int]], float]):
        self.buckets, self.times, self.factor = buckets, times, factor
        self.time_call = time_call
        self.t, self._t0 = 0.0, 0.0

    def now(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(0.0, dt)

    def before_step(self) -> None:
        self._t0 = time.perf_counter()

    def after_step(self) -> None:
        step = time.perf_counter() - self._t0
        bucket, self.buckets.last = self.buckets.last, None
        if bucket is not None:
            if bucket not in self.times:
                self.times[bucket] = self.time_call(bucket)
            step += self.times[bucket]
        self.t += self.factor * step


def dry_run_buckets(eng, stream, times: Dict, factors: Sequence[float],
                    horizon_s: float, budget_s: float = math.inf,
                    warm_s: Optional[List[float]] = None) -> set:
    """Fused-step buckets the stream reaches: the engine's own scheduler
    run over the stream with the device step stubbed out, on a
    ``ModelClock`` for the first factor and then for each further one
    while less than ``budget_s`` has gone by.  Each bucket not in ``times``
    is compiled (or loaded from the cache) and timed as it is met; the
    seconds that took are added to ``warm_s[0]``."""
    warm_s = [0.0] if warm_s is None else warm_s
    real_fn, real_shapes = eng._fused_fn, eng._fused_shapes
    stub, buckets = _NoDeviceStep(eng), _Buckets()

    def time_call(bucket) -> float:
        t = time.perf_counter()
        eng._fused_fn = real_fn
        try:
            call_bucket(eng, bucket)            # compiles, or loads
            return call_bucket(eng, bucket)     # a warm call
        finally:
            eng._fused_fn = stub
            warm_s[0] += time.perf_counter() - t

    eng._fused_fn, eng._fused_shapes = stub, buckets
    t0 = time.perf_counter()
    try:
        for i, f in enumerate(factors):
            if i and time.perf_counter() - t0 >= budget_s:
                break
            loop = OpenLoop(eng, stream,
                            ModelClock(buckets, times, f, time_call),
                            rid0=10_000_000)
            loop.run(horizon_s)
            reset_engine(eng)
        return set(buckets)
    finally:
        eng._fused_fn, eng._fused_shapes = real_fn, real_shapes


def call_bucket(eng, bucket: Tuple[int, int, int, int]) -> float:
    """Call the fused step once at ``bucket`` as the engine does, with
    padded rows only (every write lands in the sink slot, every exchange
    lane is padding); the call's seconds, arg-max read-back included."""
    b, c, p, g = bucket
    cfg, sink = eng.cfg, eng.kv.sink
    exch = eng.kv.step_plan().exchange_arrays(g)
    host = exch + (np.full((b, cfg.n_kv_heads, p), sink, np.int32),
                   np.zeros((b,), np.int32), np.zeros((b,), np.int32),
                   np.full((b, cfg.n_kv_heads, c), sink, np.int32),
                   np.zeros((b, c), np.int32), np.zeros((b, c), np.int32),
                   np.zeros((b,), np.int32))
    t0 = time.perf_counter()
    dev = eng._upload(host, 0)
    kps, vps = eng.kv.pools()
    logits, kps, vps = eng._fused_fn(eng.params, kps, vps, *dev)
    eng.kv.install_pools(kps, vps)
    np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    return time.perf_counter() - t0


def warm_stream(eng, stream, horizon_s: float, log=lambda m: None) -> set:
    """Compile (or load from the persistent cache) every fused-step bucket
    the stream reaches over ``horizon_s``: rounds of the dry run, each
    bucket warmed and timed as it is first met (``ModelClock``), until a
    round meets no new bucket, for at most ``DRY_RUN_ROUNDS`` rounds (see
    ``DRY_RUN_ROUNDS_BUDGET_S``)."""
    times: Dict = {}
    dry_s = 0.0
    for i in range(DRY_RUN_ROUNDS):
        if i >= 2 and dry_s >= DRY_RUN_ROUNDS_BUDGET_S:
            break
        t0, n0, warm_s = time.perf_counter(), len(times), [0.0]
        found = dry_run_buckets(eng, stream, times, DRY_RUN_FACTORS,
                                horizon_s, budget_s=DRY_RUN_BUDGET_S,
                                warm_s=warm_s)
        dry_s += time.perf_counter() - t0 - warm_s[0]
        log(f"setup: dry run {i + 1}: {len(found)} buckets, "
            f"{len(times) - n0} new; {time.perf_counter() - t0:.3f} s, "
            f"{warm_s[0]:.3f} s of it warm-up")
        if len(times) == n0:
            break
    return set(times)


# ------------------------------------------------------------ the open loop
class WallClock:
    def now(self) -> float:
        return time.perf_counter()

    def sleep(self, dt: float) -> None:
        time.sleep(max(0.0, dt))

    def before_step(self) -> None:
        pass

    def after_step(self) -> None:
        pass


class VirtualClock:
    """A clock on which each engine step takes ``step_s``."""

    def __init__(self, step_s: float):
        self.t, self.step_s = 0.0, step_s

    def now(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += max(0.0, dt)

    def before_step(self) -> None:
        pass

    def after_step(self) -> None:
        self.t += self.step_s


@dataclasses.dataclass
class Track:
    """One request of the stream, as the harness saw it."""
    due_s: float
    req: object
    submit_s: float
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    seen: int = 0


@dataclasses.dataclass
class StepRecord:
    start_s: float
    end_s: float
    rows: List[Tuple[int, int]]     # (first position, tokens) per row
    decode_rows: int
    prefill_tokens: int
    emitted: int
    gaps_s: List[float]             # inter-token gaps that end here
    admitted: int                   # requests taken off the queue
    queued: int                     # requests waiting after the step


class OpenLoop:
    """Submits each request of ``stream`` at the first step boundary after
    its due time and steps the engine while it holds work.  Times are
    seconds since ``start``, on ``clock``."""

    def __init__(self, eng, stream, clock, rid0: int = 0,
                 annotate: bool = False):
        self.eng, self.stream, self.clock = eng, stream, clock
        self.rid0, self.annotate = rid0, annotate
        self.next = 0
        self.tracks: List[Track] = []
        self.steps: List[StepRecord] = []
        self.t0 = clock.now()
        self._live: Dict[int, Track] = {}

    def now(self) -> float:
        return self.clock.now() - self.t0

    def _submit_due(self, now: float) -> None:
        from repro.serving import Request
        while (self.next < len(self.stream)
               and self.stream[self.next].due_s <= now):
            s = self.stream[self.next]
            # arrival 0: the engine stamps its own clock, so admission is
            # never held back by a modeled arrival time
            r = Request(rid=self.rid0 + s.index, prompt=list(s.prompt),
                        max_new_tokens=s.max_new_tokens, arrival=0.0)
            self.eng.submit(r)
            t = Track(due_s=s.due_s, req=r, submit_s=self.now())
            self.tracks.append(t)
            self._live[r.rid] = t
            self.next += 1

    def _step(self) -> None:
        eng = self.eng
        dec = [(r, r.ctx_len - 1, len(r.output)) for r in eng.running
               if not r.done]
        pre = [(r, r.prefill_pos) for r in itertools.chain(
            eng.prefilling, itertools.islice(eng.queue, eng.ecfg.max_batch))]
        queued, evicted = len(eng.queue), eng._c_evict.value
        start = self.now()
        self.clock.before_step()
        if self.annotate:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        else:
            eng.step()
        self.clock.after_step()
        end = self.now()
        rows, n_dec, n_pre = [], 0, 0
        for r, pos, n_out in dec:
            if len(r.output) > n_out:
                rows.append((pos, 1))
                n_dec += 1
        for r, pos in pre:
            n = r.prefill_pos - pos
            if n > 0:
                rows.append((pos, n))
                n_pre += n
        emitted, gaps = 0, []
        for r, *_ in itertools.chain(dec, pre):
            t = self._live.get(r.rid)
            if t is None or len(r.output) <= t.seen:
                continue
            new = len(r.output) - t.seen
            emitted += new
            if t.first_s is None:
                t.first_s = end
            else:
                gaps.append(end - t.last_s)
            t.last_s, t.seen = end, len(r.output)
            if r.done:
                del self._live[r.rid]
        admitted = queued - len(eng.queue) + int(eng._c_evict.value
                                                 - evicted)
        self.steps.append(StepRecord(start, end, rows, n_dec, n_pre,
                                     emitted, gaps, admitted,
                                     len(eng.queue)))

    def busy(self) -> bool:
        e = self.eng
        return bool(e.queue or e.running or e.prefilling)

    def run(self, until_s: float,
            stop: Optional[Callable[[], bool]] = None) -> None:
        """Drive the engine until ``until_s`` (no step starts after it),
        or until ``stop()`` holds."""
        while True:
            now = self.now()
            if now >= until_s or (stop is not None and stop()):
                return
            self._submit_due(now)
            if self.busy():
                self._step()
                continue
            if self.next >= len(self.stream):
                return
            self.clock.sleep(min(self.stream[self.next].due_s, until_s)
                             - now)


def warmed_loop(eng, sp: CellSpec, seed: int, rate: float, horizon_s: float,
                dry_horizon_s: Optional[float] = None,
                annotate: bool = False, log=lambda m: None
                ) -> Tuple["OpenLoop", int]:
    """The cell's stream at ``rate`` covering ``horizon_s``, every fused-step
    bucket its dry run (over ``dry_horizon_s``) reaches warmed, and an open
    loop over it that starts now; also the number of buckets warmed."""
    stream = loadgen.make_stream(
        sp.mix, rate, horizon_s, seed, eng.cfg.vocab_size,
        int(sp.serve["max_seq"]), int(sp.cell.get("burst", 0)))
    buckets = warm_stream(eng, stream, dry_horizon_s or horizon_s, log)
    return OpenLoop(eng, stream, WallClock(), annotate=annotate), len(buckets)


# -------------------------------------------------------------- one run
@dataclasses.dataclass
class Window:
    start_s: float
    end_s: float
    steps: List[StepRecord]
    due: List[Track]                # requests due inside the window
    submitted: List[Track]          # requests submitted inside the window

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


def window_summary(steps: Sequence[StepRecord]) -> str:
    """One log line on the window's steps: walls of the steps that carried
    prefill tokens and of decode-only ones, rows, tokens and the queue."""
    def walls(pick) -> str:
        ms = [(s.end_s - s.start_s) * 1e3 for s in steps if pick(s)]
        if not ms:
            return "0 steps"
        q = np.percentile(ms, [10, 50, 90])
        return (f"{len(ms)} steps, wall p10/p50/p90 "
                f"{q[0]:.1f}/{q[1]:.1f}/{q[2]:.1f} ms")
    if not steps:
        return "window: no steps"
    gaps = [g for s in steps for g in s.gaps_s]
    mixed = sum(len(s.gaps_s) for s in steps if s.prefill_tokens)
    return (f"window: with prefill {walls(lambda s: s.prefill_tokens)} "
            f"({mixed} gaps); "
            f"decode-only {walls(lambda s: not s.prefill_tokens)}; "
            f"mean rows {np.mean([len(s.rows) for s in steps]):.2f}; "
            f"{sum(s.emitted for s in steps)} tokens, {len(gaps)} gaps, "
            f"{sum(s.prefill_tokens for s in steps)} prefill tokens; "
            f"queue {steps[0].queued} -> {steps[-1].queued}")


class CompileCounter:
    """Counts XLA backend compiles (cache loads do not count) while on."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if self.on and event == self.EVENT:
            self.n += 1


def judge(cellp: Dict, gap: Dict, bad_lengths: int) -> Tuple[Dict, bool]:
    """Each number compared beside its limit, and whether the run is
    correct: the widest logit gap (``oracle.widest_gap`` or, for the
    control, ``oracle.control_gap``) within the cell's ``gap_limit`` over
    at least ``check_tokens`` tokens, and every finished request exactly
    as long as it asked."""
    checks = {
        "logit_gap": {"value": gap["gap"],
                      "limit": float(cellp["gap_limit"])},
        "tokens_compared": {"value": gap["tokens"],
                            "limit": int(cellp["check_tokens"])},
        "bad_lengths": {"value": bad_lengths, "limit": 0},
    }
    correct = (math.isfinite(gap["gap"])
               and gap["gap"] <= checks["logit_gap"]["limit"]
               and gap["tokens"] >= checks["tokens_compared"]["limit"]
               and bad_lengths == 0)
    return checks, correct


def run_cell(sp: CellSpec, seed: int, seconds: float, trace: bool, *,
             t_start: float, log=print) -> Dict:
    """One run of cell ``sp``; returns the result line's object."""
    cellp, serve = sp.cell, sp.serve
    ref = reference_module(sp.config)
    cfg = model_config(sp.config, ref)
    dev0 = jax.devices()[0]
    chips = len(jax.devices())
    compiles = CompileCounter()

    params = ref.make_params(sp.config, seed)
    jax.block_until_ready(params)
    check_param_layout(cfg, params)
    eng = build_engine(cfg, params, serve, telemetry=trace)
    log(f"setup: weights and engine built, "
        f"{time.perf_counter() - t_start:.3f} s since start")

    rate = float(cellp["rate"])
    lead_in = float(cellp["lead_in_s"])
    drain = float(cellp.get("drain_s", 0.0)) if sp.reports(
        "ttft_p90_ms") else 0.0
    loop, n_buckets = warmed_loop(eng, sp, seed, rate,
                                  lead_in + seconds + drain,
                                  lead_in + seconds, annotate=trace,
                                  log=log)
    log(f"setup: {n_buckets} fused-step buckets warmed, "
        f"{time.perf_counter() - t_start:.3f} s since start")
    loop.run(lead_in)
    w0 = loop.now()
    setup_s = time.perf_counter() - t_start
    rc0 = eng._c_recompiles.value
    reg0 = eng.snapshot()
    admit0 = eng.tracer.total("admit")
    n_steps0 = len(loop.steps)
    n_tracks0 = len(loop.tracks)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles.on = True
    if trace:
        opts = jax.profiler.ProfileOptions()
        # TraceMe spans (the harness's and JAX's) only: the Python tracer
        # would slow the very host work the trace measures
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    loop.run(w0 + seconds)
    t_stop = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    stop_s = time.perf_counter() - t_stop
    compiles.on = False
    win_steps = loop.steps[n_steps0:]
    w1 = win_steps[-1].end_s if win_steps else loop.now()
    reg1 = eng.snapshot()
    admit1 = eng.tracer.total("admit")
    window_recompiles = eng._c_recompiles.value - rc0
    log(f"window: {len(win_steps)} steps in {w1 - w0:.3f} s; compiles "
        f"inside the window: jit/recompiles {window_recompiles:.0f}, "
        f"backend compiles {compiles.n}")
    log(window_summary(win_steps))

    # the requests due in the window by the schedule, the same for every
    # seed
    n_due = sum(s.due_s < lead_in + seconds for s in loop.stream)

    def in_window() -> List[Track]:
        return [t for t in loop.tracks
                if lead_in <= t.due_s < lead_in + seconds]

    if drain > 0.0:
        # every request due in the window gets its first token, with the
        # open loop still offering load
        loop.run(w1 + drain, stop=lambda: loop.next >= n_due and all(
            t.first_s is not None for t in in_window()))
    window = Window(w0, w1, win_steps, in_window(),
                    [t for t in loop.tracks[n_tracks0:]
                     if t.submit_s <= w1])

    mem = dev0.memory_stats() or {}
    samples = oracle.served_samples(loop.tracks, seed,
                                    int(cellp["check_tokens"]))
    finished = [t for t in loop.tracks if t.req.done]
    bad_lengths = sum(len(t.req.output) != t.req.max_new_tokens
                      for t in finished)
    del loop, eng
    gc.collect()

    trace_sum = None
    if trace:
        t0 = time.perf_counter()
        trace_sum = trace_reduce.reduce_dir(trace_dir)
        trace_sum.window_s = w1 - w0
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: {stop_s:.3f} s to stop, "
            f"{time.perf_counter() - t0:.3f} s to reduce")

    gap = oracle.widest_gap(ref, sp.config, params, samples,
                            int(serve["max_seq"]))
    checks, correct = judge(cellp, gap, bad_lengths)

    failed = [t for t in window.due if t.first_s is None] if drain else []
    ctx = SimpleNamespace(
        spec=sp, cfg=cfg, seconds=seconds, setup_s=setup_s, window=window,
        counters={k: reg1.get(k, 0.0) - reg0.get(k, 0.0) for k in reg1},
        admit_s=admit1 - admit0, trace=trace_sum,
        device=dev0, memory=mem, peaks=peaks.for_device(dev0.device_kind),
        counts=counts)
    wanted = sp.per_layer if trace else sp.end_to_end
    metrics = {}
    for m in wanted:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips,
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    out = {"correct": bool(correct), "attempted": len(window.due),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace_sum is not None:
        device["busy_s"] = trace_sum.busy_s
        device["window_s"] = trace_sum.window_s
        out["breakdown"] = trace_sum.breakdown()
    out["checks"] = checks
    return out
