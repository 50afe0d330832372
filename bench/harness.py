"""One run of one cell: set-up, lead-in, the measured window, the check.

The window drives the program's served entry, ``PagedServer.submit`` and
``PagedServer.step``, open-loop: before each ``step()`` every request
whose due time has passed is submitted, and the tokens a step produced
are stamped on this clock when it returns (when a streaming user would
see them).  Latencies run from the time a request was due.

Everything the benchmark measures with lives under ``bench/``: the
generator, the metric arithmetic, the trace reduction, and in the
configuration's yardstick (``cell.arch``, ``bench/arch/``) the weights,
the reference and the counts.  From the program it takes the server,
the decoder's parameter layout and the config registry.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.spec import ROOT, Cell, metric_module
from bench.traffic import generate

CACHE = ROOT / ".bench_cache"
TRACE_DIR = CACHE / "trace"

_compiles = {"n": 0, "s": 0.0, "listening": False, "built": []}


def _on_duration(event: str, secs: float, fun_name: str = "?", **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["n"] += 1
        _compiles["s"] += secs
    elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        # every program built, whether then compiled or loaded from the
        # persistent cache (a cache load fires no backend compile)
        _compiles["built"].append(fun_name)


def setup_jax() -> None:
    """Persistent compile cache at a fixed path in the checkout, every
    program cached whatever its size or compile time; compile counting."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _compiles["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compiles["listening"] = True


def compiles() -> Tuple[int, float]:
    return _compiles["n"], _compiles["s"]


def programs_built() -> List[str]:
    """Names of the programs built so far in this process."""
    return list(_compiles["built"])


@dataclass
class Record:
    """One scheduled request as the client saw it."""
    due: float  # absolute, perf_counter seconds
    tier: float
    prompt: np.ndarray
    max_new: int
    rid: int = -1
    submit_t: Optional[float] = None
    stamps: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    prefill_start_t: Optional[float] = None
    finished: bool = False
    aborted: bool = False
    req: Any = None  # the scheduler's live request object


@dataclass
class RunData:
    """What a run leaves for the metric readers."""
    cell: Cell
    seed: int
    t_open: float
    t_close: float
    records: List[Record]
    setup_s: float
    #: programs built in the window (compiled, or loaded from the cache)
    compiles_in_window: List[str]
    peak_bytes: int
    device: Dict[str, Any]
    # traced runs only
    tracer_events: List[Dict[str, Any]] = field(default_factory=list)
    tracer_epoch: float = 0.0
    plans: List[Tuple[float, Any, List[Tuple[int, float]]]] = field(
        default_factory=list)
    trace: Any = None  # trace_reduce.Reduced
    trace_window: Tuple[float, float] = (0.0, 0.0)  # perf_counter
    waits: List[Tuple[float, float]] = field(default_factory=list)
    breakdown: Optional[Dict[str, Any]] = None

    @property
    def spec(self):
        return self.cell.spec

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def spans(self, name: str) -> List[Tuple[float, float]]:
        """Tracer spans ``name`` that start in the window, as absolute
        (start, seconds)."""
        out = []
        for e in self.tracer_events:
            if e.get("ph") == "X" and e["name"] == name:
                t = self.tracer_epoch + e["ts"] * 1e-6
                if self.in_window(t):
                    out.append((t, e["dur"] * 1e-6))
        return out


# -- set-up -----------------------------------------------------------------

def seed_key(seed: int):
    """A raw threefry key from a seed of any size (64-bit seeds too)."""
    import jax.numpy as jnp

    state = np.random.SeedSequence(int(seed) & (2**128 - 1)).generate_state(2)
    return jnp.asarray(state, dtype=jnp.uint32)


def build_server(cell: Cell, seed: int, tracer=None):
    """Weights from the seed (the yardstick's one jitted call), then the
    server as the configuration deploys it; the attention backend, page
    size and prefill chunk stay at the server's defaults."""
    import inspect

    import jax

    from repro.core.griffin import GriffinConfig
    from repro.serving.server import PagedServer

    arch = cell.arch
    cfg = arch.program_config(cell.config)
    w = arch.make(cell.spec, seed_key(seed))
    jax.block_until_ready(w)
    params = arch.program_params(w, cfg)
    sv = cell.config["serving"]
    page = inspect.signature(PagedServer.__init__).parameters[
        "page_size"].default
    if sv["kv_tokens"] % page:
        raise ValueError(f"kv_tokens {sv['kv_tokens']} is not a whole "
                         f"number of {page}-token pages")
    srv = PagedServer(
        cfg, params, GriffinConfig() if sv["griffin"] else None,
        num_pages=sv["kv_tokens"] // page, n_slots=sv["slots"],
        max_len=sv["max_len"], tracer=tracer,
    )
    return w, srv


def _pow2_at_least(n: int) -> int:
    w = 1
    while w < n:
        w *= 2
    return w


def warm_requests(cell: Cell, page: int, slots: int) -> List[Tuple]:
    """(prompt length, max_new, tier) of the warm-up: for each tier,
    one request per decode block-table width bucket the mix can reach
    (its prompt fills exactly that many pages at its first decode step;
    the longest also runs every prefill width), then every slot at once
    so that each slot index installs GRIFFIN buffers."""
    mix = cell.traffic
    max_len = cell.config["serving"]["max_len"]
    p_min = int(mix["prompt"]["min"])
    longest = min(generate.longest_request_tokens(mix), max_len)
    lo = _pow2_at_least(-(-(p_min + 1) // page))
    hi = _pow2_at_least(-(-longest // page))
    tiers = sorted(float(t) for t in mix["tiers"])
    out = []
    for tier in tiers:
        w = lo
        while w <= hi:
            p = min(page * w - 1, max_len - 2)
            out.append((p, 2, tier))
            w *= 2
    short = max(p_min, 1)
    for i in range(slots):
        out.append((short, 4, tiers[i % len(tiers)]))
    return out


def warm_up(srv, cell: Cell) -> None:
    """Drive the warm-up requests through the served entry, then drop
    what they left (prefix-cache pages, metrics)."""
    page, slots = srv.pcfg.page_size, srv.n_slots
    rng = np.random.default_rng(0)
    V = cell.spec.vocab
    reqs = warm_requests(cell, page, slots)
    singles, batch = reqs[:-slots], reqs[-slots:]
    for p, m, tier in singles:
        srv.submit(rng.integers(0, V, p).astype(np.int32), max_new=m,
                   tier=tier)
        srv.drain()
    for p, m, tier in batch:
        srv.submit(rng.integers(0, V, p).astype(np.int32), max_new=m,
                   tier=tier)
    srv.drain()
    srv.sched.flush_prefix()
    srv.sched.finished.clear()
    srv.reset_metrics()


# -- the window ---------------------------------------------------------------

class Loop:
    """Open-loop driver: submit what is due, step, stamp tokens."""

    def __init__(self, srv, records: List[Record],
                 on_step: Optional[Callable[[], None]] = None):
        self.srv = srv
        self.records = records
        self.next = 0
        self.active: List[Record] = []
        self.on_step = on_step
        self.waits: List[Tuple[float, float]] = []
        #: rid -> the tier this client asked for
        self.tier_of: Dict[int, float] = {}

    def _submit_due(self, now: float) -> None:
        recs = self.records
        while self.next < len(recs) and recs[self.next].due <= now:
            r = recs[self.next]
            self.next += 1
            r.submit_t = time.perf_counter()
            try:
                r.rid = self.srv.submit(r.prompt, max_new=r.max_new,
                                        tier=r.tier)
            except ValueError:
                r.aborted = True  # refused
                continue
            r.req = self.srv.sched.lookup(r.rid)
            self.tier_of[r.rid] = r.tier
            self.active.append(r)

    def _stamp(self) -> None:
        now = time.perf_counter()
        still = []
        for r in self.active:
            gen = r.req.generated
            if len(gen) > len(r.tokens):
                new = gen[len(r.tokens):]
                r.tokens.extend(int(t) for t in new)
                r.stamps.extend([now] * len(new))
            if r.req.aborted:
                r.aborted = True
            elif r.rid in self.srv.sched.finished:
                r.finished = True
            else:
                still.append(r)
        self.active = still

    def run_until(self, t_end: float) -> None:
        srv = self.srv
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self._submit_due(now)
            if srv.sched.has_work:
                if self.on_step is not None:
                    self.on_step()
                else:
                    srv.step()
                self._stamp()
            else:
                nxt = self.records[self.next].due \
                    if self.next < len(self.records) else t_end
                until = min(nxt, t_end)
                t0 = time.perf_counter()
                if until > t0:
                    time.sleep(until - t0)
                    self.waits.append((t0, time.perf_counter()))


def make_records(cell: Cell, seed: int, seconds: float,
                 t0: float) -> List[Record]:
    sched = generate.schedule(
        cell.traffic, seed, generate.horizon(cell.traffic, seconds),
        cell.spec.vocab, cell.config["serving"]["max_len"])
    return [Record(due=t0 + r.due, tier=r.tier, prompt=r.prompt,
                   max_new=r.max_new) for r in sched]


# -- end-to-end metrics --------------------------------------------------------

def pct(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(run: RunData) -> Dict[str, float]:
    t0, t1 = run.t_open, run.t_close
    due = [r for r in run.records if t0 <= r.due < t1]
    ttft = []
    for r in due:
        first = r.stamps[0] if r.stamps else None
        ttft.append(((first if first is not None and first < t1 else t1)
                     - r.due) * 1e3)
    gaps, n_tok = [], 0
    for r in run.records:
        for a, b in zip(r.stamps, r.stamps[1:]):
            if t0 <= b < t1:
                gaps.append((b - a) * 1e3)
        n_tok += sum(1 for s in r.stamps if t0 <= s < t1)
    return {
        "ttft_p90_ms": pct(ttft, 90) if ttft else float("nan"),
        "itl_p95_ms": pct(gaps, 95) if gaps else float("nan"),
        "output_tokens_per_s": n_tok / (t1 - t0),
        "setup_s": run.setup_s,
    }


# -- the check ------------------------------------------------------------------

def check_sample(run: RunData, sample_tokens: int, sample_requests: int
                 ) -> List[Record]:
    """Finished requests drawn from the seed, the longest first, until
    ``sample_tokens`` served tokens or ``sample_requests`` requests."""
    done = [r for r in run.records if r.finished and not r.aborted
            and len(r.tokens) == r.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.due))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(run.seed) & (2**64 - 1), 99])
    order = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], len(longest.tokens)
    for r in order:
        if n >= sample_tokens or len(out) >= sample_requests:
            break
        out.append(r)
        n += len(r.tokens)
    return out


#: the numbers a sample of served tokens gives, per tier
NUMBERS = ("max_logit_gap", "mean_logit_gap", "not_first_share")


def _numbers(gaps: np.ndarray) -> Dict[str, float]:
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "not_first_share": float((gaps > 0).mean())}


def gap_readings(cell: Cell, w, sample: List[Record],
                 control: bool = False) -> Dict[float, Dict]:
    """Per tier, over the sample's served positions: the gap numbers of
    the served tokens and (with ``control``) of the control's picks,
    with the token and request counts."""
    import jax.numpy as jnp

    arch, s, T = cell.arch, cell.spec, cell.config["serving"]["max_len"]
    per: Dict[float, Dict[str, list]] = {}
    for r in sample:
        seq = np.zeros((T,), np.int32)
        toks = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        seq[: len(toks)] = toks
        k = None if r.tier >= 1.0 else arch.keep(s, r.tier)
        served, ctl = arch.gaps(
            s, w, jnp.asarray(seq), jnp.int32(len(r.prompt)),
            jnp.int32(len(toks)), k=k, control=control)
        on = slice(len(r.prompt) - 1, len(toks) - 1)
        d = per.setdefault(r.tier, {"served": [], "control": [],
                                    "requests": 0})
        d["served"].append(np.asarray(served)[on])
        d["control"].append(np.asarray(ctl)[on])
        d["requests"] += 1
    out = {}
    for tier, d in per.items():
        served = np.concatenate(d["served"])
        out[tier] = {"served": _numbers(served), "tokens": len(served),
                     "requests": d["requests"]}
        if control:
            out[tier]["control"] = _numbers(np.concatenate(d["control"]))
    return out


def check(run: RunData, w) -> Tuple[bool, Dict[str, Dict[str, float]], str]:
    """``correct``, the numbers compared with their limits, and a line
    describing the sample."""
    chk = run.cell.config["check"]
    sample = check_sample(run, int(chk["sample_tokens"]),
                          int(chk["sample_requests"]))
    if not sample:
        return False, {}, "no finished request to compare"
    readings = gap_readings(run.cell, w, sample)
    numbers, ok = {}, True
    for tier, d in sorted(readings.items()):
        for name, by_tier in chk["limits"].items():
            limit = by_tier.get(str(float(tier)))
            value = d["served"][name]
            numbers[f"{name}_t{tier}"] = {"value": value, "limit": limit}
            ok = ok and limit is not None and value <= limit
    desc = "; ".join(
        f"tier {t}: {d['requests']} requests, {d['tokens']} served tokens, "
        + ", ".join(f"{k} {v}" for k, v in d["served"].items())
        for t, d in sorted(readings.items()))
    return ok, numbers, desc


# -- traced runs ------------------------------------------------------------------

def record_plans(srv, plans: list, tier_of: Dict[int, float]) -> None:
    """Keep each tick's plan (host objects only): the prefill chunk's
    (start, tokens) and each decode row's (position, tier), the tier as
    this client asked for it (``tier_of``: rid -> tier)."""
    orig = srv.sched.plan_step

    def plan_step():
        plan = orig()
        pf = plan.prefill
        plans.append((
            time.perf_counter(),
            (pf.start, len(pf.tokens)) if pf is not None else None,
            [(r.cache_len, tier_of[r.rid]) for r in plan.decode],
        ))
        return plan

    srv.sched.plan_step = plan_step


def host_spans_on_trace_clock(run: RunData, tick_marks, annots,
                              waits) -> List[Tuple[str, float, float]]:
    """Tracer spans and the loop's waits on the profiler clock, aligned
    by the ``bench_tick`` annotations (one per step, perf_counter read
    just before each)."""
    n = min(len(tick_marks), len(annots))
    if n == 0:
        return []
    offset = float(np.median([annots[i][0] - tick_marks[i] * 1e9
                              for i in range(n)]))
    out = []
    for e in run.tracer_events:
        if e.get("ph") == "X":
            t = (run.tracer_epoch + e["ts"] * 1e-6) * 1e9 + offset
            out.append((f"server: {e['name']}", t, t + e["dur"] * 1e3))
    for a, b in waits:
        out.append(("harness: waiting for the next arrival",
                    a * 1e9 + offset, b * 1e9 + offset))
    out.append(("harness: submitting and stamping tokens",
                run.trace_window[0] * 1e9 + offset,
                run.trace_window[1] * 1e9 + offset))
    return out


# -- one run ------------------------------------------------------------------------

def log_memory(phase: str, chips: int) -> None:
    """Bytes in use and the peak so far on the fullest chip, on stderr,
    so that a run shows in which phase its peak arose."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    if not any(stats):
        return
    use = max(int(st.get("bytes_in_use", 0)) for st in stats)
    peak = max(int(st.get("peak_bytes_in_use", 0)) for st in stats)
    print(f"[memory] {phase}: in use {use} B, peak so far {peak} B",
          file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, tamper: Optional[Callable] = None
             ) -> Tuple[RunData, Dict[str, Any]]:
    """Set-up, lead-in, window, metrics and check.  Returns the run and
    the result dict (without ``device``)."""
    import jax

    from repro.obs.trace import Tracer

    tracer = Tracer(annotate_jax=True) if trace else None
    w, srv = build_server(cell, seed, tracer)
    log_memory("weights and server built", cell.chips)
    warm_up(srv, cell)
    log_memory("warmed up", cell.chips)
    if tamper is not None:
        tamper(srv)
    plans: list = []
    lead = float(cell.traffic["lead_s"])
    t0 = time.perf_counter()
    records = make_records(cell, seed, seconds, t0)
    loop = Loop(srv, records)
    loop.run_until(t0 + lead)
    log_memory("lead-in served", cell.chips)
    t_open = time.perf_counter()
    n_before = len(programs_built())
    tick_marks, annots = [], None
    if trace:
        record_plans(srv, plans, loop.tier_of)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))

        def traced_step():
            tick_marks.append(time.perf_counter())
            with jax.profiler.TraceAnnotation("bench_tick"):
                srv.step()

        loop.on_step = traced_step
        loop.waits = []
        tw0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_window"):
            loop.run_until(t_open + seconds)
        tw1 = time.perf_counter()
        jax.profiler.stop_trace()
    else:
        loop.run_until(t_open + seconds)
    t_close = t_open + seconds
    n_window = programs_built()[n_before:]
    log_memory("window closed", cell.chips)
    devs = jax.devices()[: cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    for r in records:
        if r.rid >= 0:
            tl = srv.metrics.requests.get(r.rid)
            if tl is not None:
                r.prefill_start_t = tl.prefill_start_t
        r.req = None
    run = RunData(cell=cell, seed=seed, t_open=t_open, t_close=t_close,
                  records=records, setup_s=t_open - t_process,
                  compiles_in_window=n_window, peak_bytes=peak,
                  device={})
    if trace:
        run.tracer_events = list(tracer.events)
        run.tracer_epoch = tracer._epoch or 0.0
        run.plans = [p for p in plans if tw0 <= p[0] <= tw1]
        run.trace_window = (tw0, tw1)
        run.waits = loop.waits
    del srv, loop
    gc.collect()
    if trace:
        _reduce_trace(run, tick_marks)
    result = {"lateness_p99_ms": _lateness(run)}
    ok, numbers, desc = check(run, w)
    result.update(correct=ok, check=numbers, check_desc=desc)
    return run, result


def _lateness(run: RunData) -> float:
    late = [(r.submit_t - r.due) * 1e3 for r in run.records
            if r.submit_t is not None and run.in_window(r.due)]
    return pct(late, 99) if late else 0.0


def _reduce_trace(run: RunData, tick_marks) -> None:
    from bench import trace_reduce

    path = trace_reduce.find_xplane(TRACE_DIR)
    tr = trace_reduce.load(path, ("bench_window", "bench_tick"))
    kernels = {}
    for m in run.cell.per_layer:
        if m["source"] == "device_trace":
            mod = metric_module(m["name"])
            kernels.update(getattr(mod, "KERNELS", {}))
    run.trace = trace_reduce.reduce(tr, kernels)
    spans = host_spans_on_trace_clock(run, tick_marks,
                                      tr.host.get("bench_tick", []),
                                      run.waits)
    # a while op spans the ops of its body: leave it out of the list
    ops = [(k, v) for k, v in run.trace.op_seconds.items()
           if not k.lstrip("%").startswith("while")]
    top_ops = sorted(ops, key=lambda x: -x[1])[:10]
    for name, sec in top_ops:
        print(f"[trace] op {sec:.6f} s {name[:400]}", file=sys.stderr)
    run.breakdown = {
        "device_ops": [[k.split(" = ")[0], v] for k, v in top_ops],
        "idle_gaps": trace_reduce.name_gaps(run.trace.gaps, spans),
    }
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


def per_layer(run: RunData) -> Dict[str, float]:
    out = {}
    for m in run.cell.per_layer:
        v = metric_module(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = v
    return out
