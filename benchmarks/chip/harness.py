"""One run of one cell of the on-chip benchmark.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``configs/<config>.json``: the model's sizes, its
architecture, the engine and gateway settings it is served with, its SLO
limits and the limit of its correctness check) and a traffic mix
(``traffic/<traffic>.json``).  The configuration's ``"architecture"``
names a module ``archs/<architecture>.py`` that holds all of the model's
mathematics the benchmark uses: the parameter tree the weights are made
for, the plain reference that decides ``correct`` and the counts of a
step's work that the rooflines read.  This module, ``readers.py`` and
``calibrate.py`` reach the model only through it.  Each metric is a
reader of its own (``metrics/<metric>.py``) over the run record this
module builds.  Everything is found by the names in ``BENCHMARK.json``
and the configuration files: a cell, a configuration, an architecture, a
mix or a metric is added by adding files and entries.

The system under test is the program's normal serving path: one
``ServingEngine`` replica per chip behind the ``Gateway``, in wall clock
with the concurrent pump.  The benchmark makes the weights from the seed
(``weights.py``, over the architecture's parameter tree), submits the
requests itself and reads each stream as a client would, on the gateway's
clock:

requests go in on the schedule and are timed from when they were due;
traffic starts ``ramp_s`` before the window, only arrivals inside the
window are measured, and arrivals go on until every measured request has
finished or ``drain_cap_s`` has passed (a measured request still open
then has failed).

After the window the program's state is freed and the architecture's
reference (compared by ``reference.py``) decides ``correct`` from a sample
of the finished requests.  ``--trace 1`` also records a profiler trace of
the middle of the window, with the program's layer entry points annotated
from here, and reports the per-layer metrics instead of the end-to-end
ones.

What a reader finds beside the client's view: the program's own
``alise.*`` spans with their stats (``rec.timeline.program``, traced runs
only; ``trace_reduce.program_spans`` and ``within``) and the engine's
counters and gauges (``rec.gauges``: ``engine.gauges()`` once warm-up is
done and again once serving has stopped, so that a cumulative counter
reads as end - start over ramp, window and drain).
"""
from __future__ import annotations

import asyncio
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
ARCHS = HERE / "archs"
TRACE_S = 10.0                  # longest traced slice of the window
CLOCK_SLACK_S = 1.0             # see the one-clock check in run_cell
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NotOnChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ cells
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    architecture: object            # the module archs/<architecture>.py


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = REPO) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    path = root / {c["name"]: c for c in bench["configs"]}[w["config"]]["file"]
    conf = load_json(path)
    if "architecture" not in conf:
        raise KeyError(f"{path} names no architecture: give it "
                       '"architecture": "<name>" for archs/<name>.py')
    return Cell(name, w["chips"], conf,
                load_json(HERE / "traffic" / f"{w['traffic']}.json"),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)],
                architecture(conf["architecture"]))


@functools.lru_cache(maxsize=None)
def _load(path: Path, name: str):
    """The Python file at ``path`` as a module, loaded once a process."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # as an import would; dataclasses need it
    spec.loader.exec_module(mod)
    return mod


def architecture(name: str):
    """The architecture module ``archs/<name>.py``: ``params(sizes)``,
    ``summarize``, ``decode_step`` and ``prefill_chunk``."""
    path = ARCHS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no architecture {name!r}: there is no "
                                f"file {path}")
    return _load(path, f"_arch_{name}")


def reader(metric: str):
    return _load(HERE / "metrics" / f"{metric}.py", f"_metric_{metric}").read


# ------------------------------------------------------------ the record
@dataclass
class Req:
    """One request as the client saw it (gateway clock, seconds)."""
    request: object                 # the program's Request
    due: float
    measured: bool
    expected: int                   # tokens it must return
    prompt: List[int]
    submit: Optional[float] = None
    first: Optional[float] = None
    finish: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    times: List[float] = field(default_factory=list)    # each token's
                                    # arrival at the client
    end: Optional[str] = None       # finish | shed | timeout | cancel

    @property
    def done(self) -> bool:
        return self.end is not None

    @property
    def ok(self) -> bool:
        return self.end == "finish"


@dataclass
class Record:
    """Everything the metric readers read."""
    sizes: dict
    slo: dict
    seconds: float
    window: tuple                   # (start, end) on the gateway clock
    requests: List[Req]
    iters: List[tuple]              # (t, ctx_tokens, lanes, dt) in window
    pool_tokens: int                # the KV pool's capacity in tokens
    setup_s: float
    peak: object = None             # peaks.Peak of the chip
    timeline: object = None         # trace_reduce.Timeline (traced runs)
    clock_offset: float = 0.0       # perf_counter - gateway clock
    architecture: object = None     # the cell's architecture module
    gauges: dict = field(default_factory=dict)  # start, end -> gauges()

    @property
    def measured(self) -> List[Req]:
        return [r for r in self.requests if r.measured]


# ------------------------------------------------------------- counters
class Compiles:
    """Backend compiles (and persistent-cache loads) while attached."""

    def __init__(self):
        from jax._src import monitoring
        self._mon = monitoring
        self.count = self.hits = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        monitoring.register_event_duration_secs_listener(self._on_compile)
        monitoring.register_event_listener(self._on_hit)

    def _on_compile(self, name, secs, **kw):
        if name == COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += secs

    def _on_hit(self, name, **kw):
        if name == CACHE_HIT_EVENT:
            with self._lock:
                self.hits += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on_compile)
        self._mon.unregister_event_listener(self._on_hit)


def use_cache(path: Path) -> None:
    """JAX's persistent compilation cache at a fixed path of the checkout,
    keeping every program, however quick to compile, so that a run after
    the first loads all of them."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# --------------------------------------------------------------- system
def arch_config(conf: dict):
    from repro.models.config import ArchConfig
    return ArchConfig(name=conf["name"], **conf["arch"])


def build(cell: Cell, seed: int, device):
    """(gateway, engine, params): the weights from the seed over the
    cell's architecture, one warmed-up replica on ``device``, the gateway
    in front of it."""
    import jax
    from repro.core.engine import EngineConfig, ServingEngine
    from repro.core.predictor import OraclePredictor
    from repro.models.model import Model
    from repro.serving.gateway import AdmissionConfig, Gateway, GatewayConfig
    from . import weights

    conf = cell.config
    if conf["predictor"] != "oracle":
        raise ValueError(f"unknown predictor {conf['predictor']!r}")
    model = Model(arch_config(conf), **conf["model"])
    with jax.default_device(device):
        template = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = weights.make_params(template, cell.architecture,
                                     conf["arch"], seed)
    engine = ServingEngine(model, params, EngineConfig(**conf["engine"]),
                           predictor=OraclePredictor())
    places = {d for leaf in jax.tree.leaves(engine.params)
              for d in leaf.devices()}
    places |= engine.kv.pool.k.devices() | engine.kv.pool.v.devices()
    if places != {device}:
        raise AssertionError(f"replica arrays on {places}, not {device}")
    g = dict(conf["gateway"])
    gw = Gateway([engine],
                 GatewayConfig(virtual_dt=None, concurrent_pump=True,
                               router_policy=g.pop("router_policy")),
                 admission=AdmissionConfig(**g))
    return gw, engine, params


def _expected(conf: dict, prompt_len: int, out: int) -> int:
    e = conf["engine"]
    return max(1, min(out, e["max_new_tokens"],
                      e["max_seq_len"] - 1 - prompt_len))


def _request(conf, spec, due, rng):
    from repro.core.request import Request, SLOClass
    from . import traffic as T
    toks = T.prompt_tokens(rng, spec, conf["arch"]["vocab_size"])
    return Request(prompt_len=spec.prompt_len, arrival_time=due,
                   true_out_len=spec.output_len, prompt_tokens=toks,
                   slo_class=(SLOClass.INTERACTIVE if spec.interactive
                              else SLOClass.BATCH))


# ---------------------------------------------------------- annotations
def annotate(gw, engine) -> None:
    """Wrap the program's layer entry points in profiler annotations
    named ``bench:<layer entry>``, so that the trace can say what the
    host was doing while the chip idled.  Traced runs only."""
    from jax.profiler import TraceAnnotation
    from .trace_reduce import PREFIX

    def wrap(obj, attr, name, stats=None):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with TraceAnnotation(PREFIX + name, **(stats(*a) if stats else {})):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)

    def decode_stats(params, tokens, active, new_gen, new_ctx, *rest):
        act = np.asarray(active, bool)
        return {"lanes": int(act.sum()),
                "ctx": int((np.asarray(new_ctx)[act] - 1).sum())}

    def prefill_stats(params, rid, tokens, start):
        return {"tokens": len(tokens), "start": int(start)}

    wrap(gw, "submit", "Gateway.submit")
    wrap(engine, "step", "ServingEngine.step")
    wrap(engine.sched, "plan", "Scheduler.plan")
    wrap(engine.kv, "decode", "decode", decode_stats)
    wrap(engine.kv, "prefill_chunk", "prefill", prefill_stats)
    wrap(engine.kv, "offload", "kv.offload")
    wrap(engine.kv, "upload", "kv.upload")


class Tracer:
    """Profiler trace of [start, stop] on the gateway clock; started and
    stopped off the event loop so that serving goes on meanwhile."""

    def __init__(self, gw, start: float, stop: float, keep: Optional[str]):
        self.gw, self.start, self.stop = gw, start, stop
        self.dir = keep or tempfile.mkdtemp(prefix="bench-trace-")
        self.keep = keep is not None

    async def run(self):
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation
        from .trace_reduce import WINDOW
        loop = asyncio.get_running_loop()
        await asyncio.sleep(max(self.start - self.gw.now(), 0.0))
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        await loop.run_in_executor(
            None, lambda: jax.profiler.start_trace(self.dir,
                                                   profiler_options=opts))
        ann = TraceAnnotation(WINDOW)
        ann.__enter__()
        try:
            await asyncio.sleep(max(self.stop - self.gw.now(), 0.0))
        finally:
            ann.__exit__(None, None, None)
            await loop.run_in_executor(None, jax.profiler.stop_trace)

    def timeline(self):
        from . import trace_reduce
        self.gw = None              # the gateway must not outlive serving
        found = sorted(Path(self.dir).rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no trace written under {self.dir}")
        tl = trace_reduce.load(str(found[-1]))
        if not self.keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        return tl


# ------------------------------------------------------------- driving
async def _consume(gw, stream, rec: Req):
    async for ev in stream:
        t = gw.now()
        if ev.kind == "token":
            if rec.first is None:
                rec.first = t
            rec.tokens.append(int(ev.token))
            rec.times.append(t)
        elif ev.kind == "finish":
            rec.finish = t
            rec.end = "finish"
        else:
            rec.end = ev.kind


def _submit(gw, rec: Req, tasks):
    rec.submit = gw.now()
    stream = gw.submit(rec.request, now=rec.due)
    tasks.append(asyncio.ensure_future(_consume(gw, stream, rec)))


def _raise_first(results) -> None:
    """Re-raise the first failure of gathered tasks; cancelled is not
    one (open streams are cancelled when the window closes)."""
    for r in results:
        if isinstance(r, BaseException) and \
                not isinstance(r, asyncio.CancelledError):
            raise r


async def _open_loop(gw, recs: List[Req], we: float, drain: float, tasks):
    i = 0
    while True:
        now = gw.now()
        while i < len(recs) and recs[i].due <= now:
            _submit(gw, recs[i], tasks)
            i += 1
        if now >= we and (now >= we + drain or all(
                r.done for r in recs if r.measured)):
            return
        nxt = recs[i].due if i < len(recs) else now + 0.01
        await asyncio.sleep(min(max(nxt - gw.now(), 0.0), 0.01))


async def serve(gw, engine, cell: Cell, seconds: float, seed: int,
                trace: bool, keep_trace: Optional[str]):
    """Drive the window; returns (records, window, tracer, compiles
    during serving, gateway-clock offset of perf_counter)."""
    from . import traffic as T
    conf, tr = cell.config, cell.traffic
    rng = T.token_rng(seed)
    compiles = Compiles()
    gw.start_pumps()
    off = time.perf_counter() - gw.now()
    t0 = gw.now() + 0.05
    ws = t0 + tr["ramp_s"]
    we = ws + seconds
    tasks: List[asyncio.Future] = []
    recs: List[Req] = []
    tracer = None
    side = []
    if trace:
        span = min(TRACE_S, seconds)
        mid = ws + (seconds - span) / 2
        tracer = Tracer(gw, mid, mid + span, keep_trace)
        side.append(asyncio.ensure_future(tracer.run()))
    try:
        for a in T.open_loop(tr, seconds, seed):
            req = _request(conf, a.spec, ws + a.due, rng)
            recs.append(Req(req, ws + a.due, a.measured,
                            _expected(conf, req.prompt_len,
                                      req.true_out_len),
                            list(req.prompt_tokens)))
        await _open_loop(gw, recs, we, tr["drain_cap_s"], tasks)
        await asyncio.gather(*side)
    finally:
        await gw.stop_pumps()
        for t in tasks + side:
            t.cancel()
        done = await asyncio.gather(*tasks, *side, return_exceptions=True)
        n_compiles = compiles.count
        compiles.close()
    _raise_first(done)
    return recs, (ws, we), tracer, n_compiles, off


# --------------------------------------------------------------- checks
def sample(recs: List[Req], n: int, seed: int) -> List[Req]:
    """The longest finished request and up to ``n - 1`` others drawn from
    the seed."""
    done = [r for r in recs if r.ok and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens),
                                       -r.request.req_id))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[int(i)] for i in sorted(pick)]


def compared(conf: dict, recs: List[Req], n_compiles: int,
             gaps: List[float]) -> Dict[str, dict]:
    """Each number ``correct`` is decided by, beside its limit."""
    wrong = sum(1 for r in recs if r.ok and len(r.tokens) != r.expected)
    lim = conf["check"]
    return {
        "logit_gap": {"value": max(gaps) if gaps else None,
                      "limit": lim["logit_gap_limit"]},
        "wrong_token_counts": {"value": wrong, "limit": 0},
        "compiles_serving": {"value": n_compiles, "limit": 0},
    }


# ------------------------------------------------------------------ run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, cache: bool = True,
             keep_trace: Optional[str] = None, control: Optional[str] = None,
             record_hook=None, log=print) -> dict:
    """One run: returns the result object (the last line of stdout).
    Raises ``NotOnChip`` before building anything where there is no TPU
    or too few chips.  ``control`` (a precision of ``reference.py``, for
    calibration only) also reads the control's widest gap over the same
    sample, into ``out["control"]``; ``record_hook`` is handed the run
    record before the result is made."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NotOnChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                        f"JAX found {len(devs)} {dev.platform} device(s) "
                        f"({dev.device_kind})")
    if cell.chips != 1:
        raise NotImplementedError("cells of more than one chip")
    from . import peaks, reference
    peak = peaks.peak_of(dev.device_kind) if require_tpu else None
    if cache:
        use_cache(HERE / ".jax_cache")
    conf = cell.config

    setup = Compiles()
    gw, engine, params = build(cell, seed, dev)
    gauges = {"start": dict(engine.gauges())}
    if trace:
        annotate(gw, engine)
    setup_s = time.perf_counter() - t_start
    setup.close()
    log(f"[bench] {cell.name} seed {seed}: set-up {setup_s:.2f}s "
        f"({setup.count} programs, {setup.seconds:.2f}s compiling or "
        f"loading, {setup.hits} from the cache)")

    recs, window, tracer, n_compiles, off = asyncio.run(
        serve(gw, engine, cell, seconds, seed, trace, keep_trace))
    gauges["end"] = dict(engine.gauges())
    ws, we = window
    iters = [(t - off, c, b, dt) for t, c, b, dt in engine.iter_times
             if ws <= t - off < we]
    # one clock: the program stamps a request's first plan before its first
    # token reached the client, and not long before it was due (the pump
    # stamps a step with the time it asked for it, which may precede a
    # submit the step's mailbox drain picked up by a stall of the loop)
    for r in recs:
        q = r.request
        if q.first_scheduled_time is not None and r.first is not None and \
                not (r.due - CLOCK_SLACK_S <= q.first_scheduled_time
                     <= r.first):
            raise AssertionError(
                f"request {q.req_id}: scheduled at {q.first_scheduled_time}"
                f", due {r.due}, first token at the client {r.first}")
    pc = engine.kv.pool.cfg         # one page of the pool is a scratch page
    pool_tokens = (pc.num_pages - 1) * pc.page_size
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    picked = sample(recs, conf["check"]["sample_requests"], seed)
    samples = [(r.prompt, r.tokens) for r in picked]
    timeline = tracer.timeline() if tracer is not None else None

    del gw, engine, params          # free the program's state before the
    gc.collect()                    # reference takes the chip
    t_ref = time.perf_counter()
    ref_args = (conf["arch"], seed, samples, conf["engine"]["max_seq_len"],
                conf["check"]["sample_requests"])
    summarize = cell.architecture.summarize
    gaps, control_gaps = [], None
    if samples and control:
        gaps, control_gaps = reference.control_gaps(summarize, *ref_args,
                                                    control)
    elif samples:
        gaps = reference.served_gaps(summarize, *ref_args)
    log(f"[bench] reference over {len(samples)} requests "
        f"({sum(len(s) for _, s in samples)} served tokens) took "
        f"{time.perf_counter() - t_ref:.2f}s; widest gaps "
        f"{[round(g, 5) for g in gaps]}")
    checks = compared(conf, recs, n_compiles, gaps)
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    record = Record(conf["arch"], conf["slo"], seconds, window, recs,
                    iters, pool_tokens, setup_s, peak, timeline, off,
                    cell.architecture, gauges)
    if record_hook is not None:
        record_hook(record)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    attempted = len(record.measured)
    failed = sum(1 for r in record.measured if not r.ok)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if timeline is not None:
        from . import trace_reduce as TR
        device["busy_s"] = TR.busy_s(timeline)
        device["window_s"] = timeline.window_s
        out["breakdown"] = {"device_ops": TR.top_ops(timeline),
                            "idle_gaps": TR.idle_by_host_span(timeline)}
    if control_gaps is not None:
        out["control"] = {"precision": control, "gaps": control_gaps,
                          "served_gaps": gaps}
    out["compared"] = checks
    return out
