"""Host spans on the profiler's clock (``serving/observability/span.py``):
a CPU profiler trace of a tiny engine holds the ``alise.*`` spans nested as
documented, an attached bus gets the same spans, every serving program is
named ``jit_alise_*``, named scopes change op metadata only, and the
first-token stamps of a gateway run are ordered on one clock."""
import asyncio
import contextlib
import functools
import gc
import re
import tempfile
from collections import Counter
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor
from repro.core.request import Request
from repro.models.model import Model
from repro.serving.gateway import Gateway, GatewayConfig
from repro.serving.kv_cache import named_program, paged_decode, paged_prefill
from repro.serving.observability import EventBus, span


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, **kw):
    return ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=8, kv_backend="paged",
        prefill_chunk=16, quantize_offload=False, **kw),
        predictor=OraclePredictor())


def _requests(n=3, prompt=10, out=6):
    return [Request(prompt_len=prompt, arrival_time=0.0, true_out_len=out,
                    prompt_tokens=list(range(1, prompt + 1)))
            for _ in range(n)]


class Traced:
    """The host events of one profiler trace: ``alise.*`` spans by thread
    line as (name, start_ns, end_ns, stats), and the programs' names."""

    def __init__(self, path: str):
        self.lines, self.modules = [], set()
        with warnings.catch_warnings():     # the stats type lacks __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                for line in plane.lines:
                    spans = []
                    for e in line.events:
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            self.modules.add(stats["hlo_module"])
                        if e.name.startswith("alise."):
                            spans.append((e.name, e.start_ns, e.end_ns,
                                          stats))
                    if spans:
                        self.lines.append(spans)

    def named(self, name):
        return [s for line in self.lines for s in line if s[0] == name]

    def parents(self, child, parent):
        """For each ``child`` span, whether a ``parent`` span on the same
        thread line contains it."""
        out = []
        for line in self.lines:
            outer = [s for s in line if s[0] == parent]
            for c in (s for s in line if s[0] == child):
                out.append(any(p[1] <= c[1] and c[2] <= p[2]
                               for p in outer))
        return out


@pytest.fixture(scope="module")
def traced(model_and_params):
    """A tiny paged engine with a bus, three requests served under the
    profiler."""
    _, model, params = model_and_params
    eng = _engine(model, params)
    bus = EventBus()
    eng.attach_bus(bus, "e0")
    out = tempfile.mkdtemp(prefix="spans-")
    jax.profiler.start_trace(out)
    try:
        eng.serve(_requests())
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(out).rglob("*.xplane.pb"))
    return Traced(str(path)), bus.snapshot(), eng


@pytest.mark.parametrize("child,parent", [
    ("alise.sched.plan", "alise.engine.step"),
    ("alise.engine.prefill", "alise.engine.step"),
    ("alise.engine.decode", "alise.engine.step"),
    ("alise.predictor.feedback", "alise.engine.step"),
    ("alise.decode.stage", "alise.engine.decode"),
    ("alise.decode.dispatch", "alise.engine.decode"),
    ("alise.decode.sync", "alise.engine.decode"),
    ("alise.decode.accept", "alise.engine.decode"),
])
def test_spans_nest_as_documented(traced, child, parent):
    tr, _, _ = traced
    inside = tr.parents(child, parent)
    assert inside and all(inside), (child, parent, inside)


def test_decode_and_prefill_spans_carry_their_stats(traced):
    tr, _, eng = traced
    dec = tr.named("alise.engine.decode")
    assert dec and all(set(s[3]) == {"lanes", "ctx", "width"} for s in dec)
    # the iteration ring saw the same lanes and context tokens
    assert [(s[3]["lanes"], s[3]["ctx"]) for s in dec] == \
        [(b, c) for _, c, b, _ in eng.iter_times]
    assert all(s[3]["lanes"] >= 1 and s[3]["ctx"] >= 10 for s in dec)
    # the backend counted a step at each width the spans gathered
    widths = Counter(s[3]["width"] for s in dec)
    assert widths == +Counter(eng.kv.decode_width_steps)
    pre = tr.named("alise.engine.prefill")
    assert [s[3] for s in pre] == [{"tokens": 10, "start": 0}] * 3
    assert tr.named("alise.gc")


def test_attached_bus_gets_the_same_spans(traced):
    tr, events, eng = traced
    for kind, name in (("decode_iter", "alise.engine.decode"),
                       ("prefill_chunk", "alise.engine.prefill")):
        on_bus = [e for e in events if e.kind == kind]
        spans = sorted(tr.named(name), key=lambda s: s[1])
        assert len(on_bus) == len(spans) > 0
        for e, s in zip(on_bus, spans):
            # one timing: the bus event lasts as long as the span
            assert e.dur == pytest.approx((s[2] - s[1]) * 1e-9, abs=2e-4)
    # the prefill ring is timed by the same spans
    assert [n for _, n, _ in eng.prefill_times] == [10, 10, 10]


def test_serving_programs_are_named(traced):
    tr, _, _ = traced
    assert {"jit_alise_decode", "jit_alise_prefill_chunk"} <= tr.modules
    assert not any("_unknown" in m or "lambda" in m for m in tr.modules)


def test_span_emits_its_timing_once_on_exit():
    bus = EventBus()
    with span("test.block", bus, 100.0, n=3) as sp:
        sp.emit("decode_iter", batch=3)
        sp.emit("prefill_chunk", tokens=8)
    evs = bus.snapshot()
    assert [e.kind for e in evs] == ["decode_iter", "prefill_chunk"]
    assert all(e.t == 100.0 + sp.t0 and e.dur == sp.dur for e in evs)
    assert evs[0].data == {"batch": 3}
    with span("test.block") as sp:          # no bus: nothing is queued
        sp.emit("decode_iter", batch=1)
    with pytest.raises(ValueError):         # a failed block emits nothing
        with span("test.block", bus) as sp:
            sp.emit("decode_iter", batch=2)
            raise ValueError
    assert len(bus.snapshot()) == 2


META = re.compile(r",?\s*metadata=\{[^}]*\}")


def _without_metadata(hlo: str) -> str:
    return META.sub("", hlo.split("\nFileNames")[0])


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_named_scopes_change_metadata_only(model_and_params, monkeypatch,
                                           step):
    cfg, model, _ = model_and_params
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, page, pages, maxp, C = 2, 16, 9, 4, 16
    kv = {k: jax.ShapeDtypeStruct(
        (cfg.num_layers, pages, page, cfg.num_kv_heads, cfg.hd),
        model.kv_dtype) for k in "kv"}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    if step == "decode":
        program = ("alise_decode", functools.partial(paged_decode, model),
                   dict(interpret=True, max_new_tokens=8, max_seq_len=64))
        args = (params, kv, i32(B, 1), i32(B, maxp), i32(B), i32(B), i32(B),
                jax.ShapeDtypeStruct((B,), bool), i32(B), i32(B), i32(B),
                i32(B), jax.ShapeDtypeStruct((2,), jnp.uint32))
    else:
        program = ("alise_prefill_chunk",
                   functools.partial(paged_prefill, model),
                   dict(interpret=True))
        args = (params, kv, i32(1, C), i32(1, maxp), i32(C), i32(C), i32(),
                i32())

    def compiled():
        # a new function each time, so that jit traces it afresh
        name, fn, static = program
        return jax.jit(named_program(name, fn, **static),
                       donate_argnums=(1,)).lower(*args).compile().as_text()
    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "/attention/" in scoped and "/ffn/" in scoped
    assert "/lm_head/" in scoped
    assert "/attention/" not in plain
    assert _without_metadata(scoped) == _without_metadata(plain)


def test_first_token_stamps_are_ordered_on_one_clock(model_and_params):
    """due - 1 s <= first plan <= engine hand-off <= the client's first
    token, for every request of a wall-clock gateway run."""
    _, model, params = model_and_params
    gw = Gateway([_engine(model, params)],
                 GatewayConfig(virtual_dt=None, concurrent_pump=True))
    firsts = {}

    async def consume(stream):
        async for ev in stream:
            if ev.kind == "token" and stream.request.req_id not in firsts:
                firsts[stream.request.req_id] = gw.now()

    async def run():
        gw.start_pumps()
        tasks, due = [], []
        for i, req in enumerate(_requests(n=4, out=4)):
            t = gw.now() + 0.02 * i
            await asyncio.sleep(max(t - gw.now(), 0.0))
            due.append((req, t))
            tasks.append(asyncio.ensure_future(
                consume(gw.submit(req, now=t))))
        await asyncio.wait_for(asyncio.gather(*tasks), 120)
        await gw.stop_pumps()
        return due
    for req, t in asyncio.run(run()):
        sched, emit = req.first_scheduled_time, req.first_token_emit_time
        assert t - 1.0 <= sched <= emit <= firsts[req.req_id], (
            t, sched, emit, firsts[req.req_id])
        assert req.first_token_time <= emit
