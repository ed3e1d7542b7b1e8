"""One run of one cell: set-up, the measured window, the check, the metrics.

`run_cell` is the whole run; `run.py` is its command line. Tests drive
`run_cell` directly with a small configuration on the CPU.

Set-up: traffic from the seed, weights on the device from the seed, the
engine, every shape the cell's traffic uses run once (the chunked
prefill, the first-token sampler for each count of rows that can finish
prefill in one tick, the fused decode and sample), and what the traffic
needs in place before it starts: the shared prefixes prefilled into the
prefix cache, or every closed-loop client's first request past its
prefill. The window then runs for `seconds`; nothing may compile in it.

Afterwards the program's state is freed and the plain reference checks a
sample of the finished requests (`check.py`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, loadgen, peaks, sut

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"

#: how long after the window closes an open-loop run waits for the
#: requests that were due in it; one still unfinished then has failed
DRAIN_LIMIT_S = 60.0
#: seconds of the window a traced run records: a v5e trace of a full
#: 51-second window of MiniCPM-2B decode outgrows the profiler's event
#: buffer (the device line stopped after about 29 s)
TRACE_S = 15.0


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def metrics_for(entries: List[dict], workload: str) -> List[dict]:
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def load_cell(workload: str, bench_path: Path = BENCHMARK) -> Cell:
    bench = json.loads(bench_path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_path.name}; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    spec = json.loads((ROOT / conf["file"]).read_text())
    return Cell(workload, spec, loadgen.load_mix(w["traffic"]), w["chips"],
                metrics_for(bench["end_to_end"], workload),
                metrics_for(bench["per_layer"], workload),
                check.load_limits(workload))


# -- request bookkeeping ------------------------------------------------------

@dataclasses.dataclass
class Track:
    """Host-clock history of one request."""
    req: loadgen.Req
    due: float                  # when it was due (closed loop: sent)
    sent: float
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    cut: bool = False           # still being served when the window closed
    prefix_hit: int = 0


class Ledger:
    """Every request sent and every token event, by engine request id."""

    def __init__(self, engine, span):
        self.eng = engine
        self.span = span
        self.tracks: Dict[int, Track] = {}
        self.on_finish: Optional[Callable[[Track, float], None]] = None

    def send(self, req: loadgen.Req, due: float) -> int:
        with self.span("bench.submit"):
            rid = self.eng.submit(sut.request(req.prompt,
                                              req.max_new_tokens))
        self.tracks[rid] = Track(req, due, time.perf_counter())
        return rid

    def step(self) -> float:
        with self.span("bench.step"):
            events = self.eng.step()
        now = time.perf_counter()
        for ev in events:
            tr = self.tracks.get(ev.id)
            if tr is None:
                continue
            for t in ev.new_tokens:
                tr.tokens.append(int(t))
                tr.times.append(now)
            if ev.finished:
                tr.finished = ev.finish_reason == "length"
                tr.prefix_hit = ev.prefix_hit_tokens
                if self.on_finish is not None:
                    self.on_finish(tr, now)
        return now


class TickLog:
    """What each engine tick did, read from the scheduler's slots before
    and after the step: the prefill chunks (start position, tokens) and
    the decode rows (context length). Kept only in a traced run, for the
    operation and byte counts of the per-layer metrics."""

    def __init__(self, engine):
        self.eng = engine
        self.prefill: List[tuple] = []       # (start, take)
        self.decode_steps: List[List[int]] = []
        self.decode_tokens = 0

    def before(self):
        out = {}
        for s in self.eng.sched.slots:
            if s is not None and not s.done:
                out[s.request.id] = (s.prefill_pos, s.prefilling,
                                     s.scheduled, s.cache_len,
                                     s.request.max_new_tokens)
        return out

    def after(self, before: dict):
        ctx = []
        for s in self.eng.sched.slots:
            if s is None:
                continue
            rid = s.request.id
            if rid in before:
                pos, prefilling, sched, clen, mx = before[rid]
                if prefilling and s.prefill_pos > pos:
                    self.prefill.append((pos, s.prefill_pos - pos))
                elif not prefilling and 0 < sched < mx:
                    ctx.append(clen + 1)
            elif s.prefill_pos > s.prefix_hit:
                self.prefill.append((s.prefix_hit,
                                     s.prefill_pos - s.prefix_hit))
        # rows released at dispatch (their last token) left the slots
        for rid, (pos, prefilling, sched, clen, mx) in before.items():
            if (not prefilling and 0 < sched < mx
                    and not any(s is not None and s.request.id == rid
                                for s in self.eng.sched.slots)):
                ctx.append(clen + 1)
        if ctx:
            self.decode_steps.append(ctx)
            self.decode_tokens += len(ctx)


# -- the run ------------------------------------------------------------------

def check_devices(chips: int):
    """The devices of a measured run: a TPU with enough chips, or exit."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found {devs[0].platform!r} "
                         f"devices only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def compile_counter():
    """A list that grows by one for every program XLA compiles from now on
    (read its length before and after the window)."""
    import jax
    seen = []

    def listener(event, duration_secs=None, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def warm_up(eng, cell: Cell):
    """Run every program variant the window will use once.

    The engine re-uploads its lengths and block tables from host mirrors
    on ticks that change them, and those uploads carry another sharding
    than the compiled steps' own outputs, so each step has one compiled
    variant per mix of the two. Each runs here as a no-op (no row valid),
    then the first-token sampler for every count of rows that can finish
    prefill in one tick, then one request through the engine's own path."""
    import jax
    import jax.numpy as jnp
    ex = eng.ex
    eng_cfg = cell.spec["engine"]
    slots, chunk = eng_cfg["slots"], eng_cfg["prefill_chunk"]
    idle = np.zeros(slots, np.int32)
    keys = jnp.stack([jax.random.PRNGKey(0)] * slots)
    temps = jnp.asarray(np.zeros(slots, np.float32))
    topks = jnp.asarray(np.zeros(slots, np.int32))
    block = jnp.asarray(np.zeros((1, chunk), np.int64), jnp.int32)
    lg = None
    for lengths_uploaded in (False, True):
        for tables_uploaded in (False, True):
            for step in ("decode", "prefill"):
                ex._lengths_dirty = lengths_uploaded
                ex._tables_dirty = tables_uploaded
                ex.flush()
                if step == "decode":
                    ex.decode_and_sample(idle, keys, temps, topks)
                else:
                    lg = ex.prefill(0, block, 0)
    key = jax.random.fold_in(jax.random.PRNGKey(0), 1)
    for r in range(1, slots + 1):
        ex.seed_tokens(list(range(r)), [lg] * r, jnp.stack([key] * r),
                       jnp.asarray(np.zeros(r, np.float32)),
                       jnp.asarray(np.zeros(r, np.int32)))
    eng.submit(sut.request([1] * (chunk + 1), 3))
    for _ in eng.events():
        pass
    jax.block_until_ready(ex.cache)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices=None, backend: Optional[str] = None,
             policy_name: Optional[str] = None,
             limit_override=None) -> dict:
    """The whole run; returns the result object of the last line.

    `backend` / `policy_name` replace the configuration's (tests run the
    interpreter; the control runs the program's lower-precision path);
    `limit_override` replaces the limits file (readings for a new limit)."""
    import jax

    spec, mix = cell.spec, cell.mix
    eng_cfg = spec["engine"]
    cfg = sut.model_config(spec)
    pol = sut.policy(spec, backend=backend, name=policy_name)
    traffic = loadgen.generate(mix, seed, seconds, spec["vocab_size"],
                               eng_cfg["slots"])
    compiles = compile_counter()

    t0 = time.perf_counter()
    params = sut.build_params(spec, cfg, pol, seed)
    jax.block_until_ready(params)
    log(f"setup: weights {sum(a.nbytes for a in jax.tree.leaves(params))} "
        f"bytes on the device in {time.perf_counter() - t0:.3f} s")
    eng = sut.build_engine(spec, cfg, pol, params, seed)
    del params
    t0 = time.perf_counter()
    warm_up(eng, cell)
    log(f"setup: warm-up {time.perf_counter() - t0:.3f} s")

    span = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    ledger = Ledger(eng, span)
    ticks = TickLog(eng) if trace else None
    closed = mix["loop"] == "closed"
    if traffic.prefixes:
        t0 = time.perf_counter()
        for p in traffic.prefixes:
            eng.submit(sut.request(p, 1))
        for _ in eng.events():
            pass
        log(f"setup: {len(traffic.prefixes)} shared prefixes of "
            f"{len(traffic.prefixes[0])} tokens prefilled in "
            f"{time.perf_counter() - t0:.3f} s")
    queues = {}
    close_at = [math.inf]       # a closed-loop client sends until then
    if closed:
        queues = {c: traffic.client_queue(c)
                  for c in range(eng_cfg["slots"])}

        def next_request(tr, now):
            c = tr.req.client
            if now < close_at[0] and queues[c]:
                ledger.send(queues[c].pop(0), now)
        ledger.on_finish = next_request
        for c, q in queues.items():
            ledger.send(q.pop(0), time.perf_counter())
        # fill: every client's first request past its prefill
        while any(s is not None and s.prefilling for s in eng.sched.slots) \
                or eng.sched.pending:
            ledger.step()
    jax.block_until_ready(eng.ex.cache)

    # -- the window -----------------------------------------------------------
    n_compiles = len(compiles)
    pending = [] if closed else list(traffic.requests)
    lateness = []

    def drive(w0, until, log_ticks):
        """Step the engine (an open loop sending what falls due) until
        the host clock reaches `until`."""
        while True:
            now = time.perf_counter()
            while pending and w0 + pending[0].due_s <= now:
                r = pending.pop(0)
                rid = ledger.send(r, w0 + r.due_s)
                lateness.append(ledger.tracks[rid].sent - (w0 + r.due_s))
            if now >= until:
                return
            if closed or eng.has_work():
                before = log_ticks.before() if log_ticks else None
                ledger.step()
                if log_ticks:
                    log_ticks.after(before)
            else:
                wait = (w0 + pending[0].due_s - now) if pending else (
                    until - now)
                with span("bench.idle"):
                    time.sleep(max(0.0, min(wait, until - now)))

    if trace:
        tdir = HERE / ".traces" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    w0 = time.perf_counter()
    setup_s = w0 - t_start
    w1 = close_at[0] = w0 + seconds
    if trace:
        # the profiler keeps a bounded number of device events: trace the
        # window's first TRACE_S seconds, then run the rest untraced
        with span("bench.window"):
            drive(w0, min(w1, w0 + TRACE_S), ticks)
            jax.block_until_ready(eng.ex.cache)
        jax.profiler.stop_trace()
    drive(w0, w1, None)
    due_in_window = [tr for tr in ledger.tracks.values() if tr.sent >= w0]
    in_window_compiles = len(compiles) - n_compiles
    log(f"window: {seconds} s, {in_window_compiles} compilations inside it")

    # -- after the window -----------------------------------------------------
    failed = 0
    if closed:
        # the answers cut at the close are served tokens all the same:
        # the check reads them with the finished ones
        ledger.on_finish = None
        for rid, tr in ledger.tracks.items():
            tr.cut = not tr.finished
            eng.abort(rid)
        for _ in eng.events():
            pass
    else:
        close = time.perf_counter()
        backlog = sum(not tr.finished for tr in due_in_window)
        deadline = close + DRAIN_LIMIT_S
        while (any(not tr.finished for tr in due_in_window)
               and time.perf_counter() < deadline and eng.has_work()):
            ledger.step()
        failed = sum(not tr.finished for tr in due_in_window)
        log(f"backlog: {backlog} requests unfinished at the close, drained "
            f"in {time.perf_counter() - close:.3f} s")
    devs = devices or jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                   default=0)
    counters = eng.stats()
    eng = ledger.eng = None
    if ticks:
        ticks.eng = None
    gc.collect()

    e2e = end_to_end(cell, ledger, w0, w1, setup_s, closed, due_in_window)
    log(f"traffic: {len(due_in_window)} requests sent in the window, "
        f"{sum(tr.finished for tr in due_in_window)} finished, {failed} "
        f"failed; generator lateness p50/p99/max "
        f"{_pcts(lateness, (50, 99, 100))} s")
    log(f"engine: {json.dumps(_plain(counters))}")

    # -- correctness ----------------------------------------------------------
    t0 = time.perf_counter()
    served = [tr for tr in ledger.tracks.values()
              if tr.finished or (tr.cut and tr.tokens)]
    limits = limit_override or cell.limits
    verdict = check.compare(cell, seed, served, limits)
    verdict["compared"]["failed_requests"] = [failed, 0]
    verdict["compared"]["compilations_in_window"] = [in_window_compiles, 0]
    correct = (verdict["correct"] and failed == 0)
    log(f"check: reference over {verdict['requests']} requests "
        f"({verdict['tokens']} served tokens) in "
        f"{time.perf_counter() - t0:.3f} s; argmax agreement "
        f"{verdict['argmax_agreement']!r}")

    dev = devs[0]
    result = {"correct": bool(correct),
              "attempted": len(due_in_window),
              "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": int(mem_peak)}}
    if trace:
        from . import xplane
        red = xplane.reduce(xplane.find_trace(tdir), chips=len(devs))
        shutil.rmtree(tdir, ignore_errors=True)
        result["device"]["busy_s"] = red.busy_s
        result["device"]["window_s"] = red.window_s
        ctx = MetricContext(cell, red, ticks, ledger, counters,
                            peaks.chip_peaks(dev.device_kind), due_in_window)
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e and e2e[m["name"]] is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    for name, (value, limit) in verdict["compared"].items():
        log(f"compared: {name} {value!r} limit {limit!r}")
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in verdict["compared"].items()}
    return result


def _pcts(xs, qs):
    if not xs:
        return "none"
    return "/".join(f"{np.percentile(xs, q):.4f}" for q in qs)


def _plain(d):
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float, str, bool)) or v is None}


def percentile(xs, q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear interpolation); None if empty."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def end_to_end(cell: Cell, ledger: Ledger, w0, w1, setup_s, closed,
               due_in_window) -> dict:
    """The end-to-end metrics, all from the host clock."""
    out = {"setup_s": setup_s}
    gaps, tokens = [], 0
    tracks = list(ledger.tracks.values()) if closed else due_in_window
    for tr in tracks:
        ts = tr.times
        tokens += sum(w0 <= t <= w1 for t in ts)
        for a, b in zip(ts, ts[1:]):
            if (w0 <= b <= w1) if closed else True:
                gaps.append(b - a)
    ttft = [tr.times[0] - tr.due for tr in due_in_window if tr.times]
    out["itl_p95_ms"] = (None if not gaps
                         else 1e3 * percentile(gaps, 95))
    out["ttft_p90_ms"] = (None if not ttft or closed
                          else 1e3 * percentile(ttft, 90))
    out["output_tok_s"] = tokens / (w1 - w0) if tokens else None
    log(f"latency: {len(ttft)} first tokens, ttft p50/p90/max "
        f"{_pcts(ttft, (50, 90, 100))} s; {len(gaps)} token gaps, itl "
        f"p50/p95/max {_pcts(gaps, (50, 95, 100))} s; {tokens} tokens in "
        f"the window")
    return out


# -- per-layer metrics --------------------------------------------------------

@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    trace: object            # xplane.Reduction
    ticks: TickLog
    ledger: Ledger
    counters: dict
    peaks: dict
    due_in_window: list


def read_metric(name: str, ctx: MetricContext):
    """Run `metrics/<name>.py`'s `read(ctx)`: a number, or None when the
    run holds nothing for it to read."""
    path = HERE / "metrics" / f"{name}.py"
    modname = "onchip.metrics." + name.replace(".", "__")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return None
    return float(value)
