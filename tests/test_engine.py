"""Real-engine integration: the strongest system invariant — scheduling must
never change greedy outputs — plus swap/recompute/quantized paths."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.engine import EngineConfig, ServingEngine
from repro.core.predictor import OraclePredictor
from repro.core.quantization import kv_bytes_per_token
from repro.core.request import Request, reset_request_counter
from repro.models.model import Model


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_smoke_config("granite-3-8b")
    model = Model(cfg, attn_chunk=32, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, outs=(40, 40, 3, 3, 3, 3), seed=0):
    rng = np.random.default_rng(seed)
    reset_request_counter()
    reqs = []
    for out in outs:
        plen = int(rng.integers(6, 12))
        reqs.append(Request(prompt_len=plen, arrival_time=0.0,
                            true_out_len=out,
                            prompt_tokens=rng.integers(
                                2, cfg.vocab_size, plen).tolist()))
    return reqs


def _reference_outputs(cfg, model, params):
    reqs = _requests(cfg)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=8, max_seq_len=64, max_new_tokens=48, strategy="vllm",
        quantize_offload=False), predictor=OraclePredictor())
    eng.serve(reqs)
    return {r.req_id: list(r.output_tokens) for r in reqs}


def _staged_run(cfg, model, params, strategy, quant):
    bpt = kv_bytes_per_token(cfg.num_layers, cfg.num_kv_heads, cfg.hd)
    reqs = _requests(cfg)
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=48, strategy=strategy,
        quantize_offload=quant, hbm_bytes=2 * 55 * bpt),
        predictor=OraclePredictor())
    t = 0.0
    for r in reqs[:2]:
        eng.submit(r, t)
    for _ in range(5):
        eng.step(t)
        t += 0.1
    for r in reqs[2:]:
        eng.submit(r, t)
    for _ in range(800):
        if not eng.sched.live:
            break
        eng.step(t)
        t += 0.1
    assert not eng.sched.live, "engine did not drain"
    return reqs, eng


def test_preemption_invariance_swap(model_and_params):
    cfg, model, params = model_and_params
    ref = _reference_outputs(cfg, model, params)
    reqs, eng = _staged_run(cfg, model, params, "alise", quant=False)
    assert sum(r.preempt_count for r in reqs) > 0
    for r in reqs:
        assert ref[r.req_id] == list(r.output_tokens)


def test_preemption_invariance_recompute(model_and_params):
    cfg, model, params = model_and_params
    ref = _reference_outputs(cfg, model, params)
    reqs, eng = _staged_run(cfg, model, params, "alise-recompute",
                            quant=False)
    assert sum(r.preempt_count for r in reqs) > 0
    assert sum(r.recompute_tokens for r in reqs) > 0
    for r in reqs:
        assert ref[r.req_id] == list(r.output_tokens)


def test_quantized_swap_bounded_divergence(model_and_params):
    cfg, model, params = model_and_params
    ref = _reference_outputs(cfg, model, params)
    reqs, eng = _staged_run(cfg, model, params, "alise", quant=True)
    total = sum(len(ref[r.req_id]) for r in reqs)
    mismatched = 0
    for r in reqs:
        a, b = ref[r.req_id], list(r.output_tokens)
        mismatched += sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
    assert mismatched / total < 0.5     # int8 KV: bounded token divergence


def test_engine_completes_everything(model_and_params):
    cfg, model, params = model_and_params
    reqs = _requests(cfg, outs=(5, 7, 9, 3))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, max_seq_len=64, max_new_tokens=16, strategy="alise"),
        predictor=OraclePredictor())
    eng.serve(reqs)
    assert all(r.done for r in reqs)
    assert all(r.generated == r.true_out_len for r in reqs)


def test_fitted_latency_model_sane(model_and_params):
    cfg, model, params = model_and_params
    reqs = _requests(cfg, outs=(10, 10, 10, 10))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, max_seq_len=64, max_new_tokens=16, strategy="vllm"),
        predictor=OraclePredictor())
    eng.serve(reqs)
    lm = eng.fit_latency_model()
    assert lm.t0 >= 0 and lm.beta > 0


def test_profiling_rings_bounded(model_and_params):
    """iter_times / prefill_times are ring buffers: long-running gateway
    serves must not grow them without bound."""
    cfg, model, params = model_and_params
    reqs = _requests(cfg, outs=(20, 20, 20, 20))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=24, strategy="alise",
        profile_window=8), predictor=OraclePredictor())
    eng.serve(reqs)
    assert len(eng.iter_times) <= 8
    assert len(eng.prefill_times) <= 8
    lm = eng.fit_latency_model()                      # still fittable from
    assert lm.beta >= 0 and lm.t0 >= 0                # the ring tail alone


def test_mamba_engine_state_swap():
    """SSM archs swap constant-size state instead of KV (DESIGN §5)."""
    cfg = get_smoke_config("mamba2-2.7b")
    model = Model(cfg, ssd_chunk=8, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _requests(cfg, outs=(12, 4, 4))
    ref_eng = ServingEngine(model, params, EngineConfig(
        max_slots=4, max_seq_len=64, max_new_tokens=16, strategy="vllm",
        quantize_offload=False), predictor=OraclePredictor())
    ref_eng.serve(reqs)
    ref = {r.req_id: list(r.output_tokens) for r in reqs}

    reqs2 = _requests(cfg, outs=(12, 4, 4))
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=64, max_new_tokens=16, strategy="alise",
        quantize_offload=False), predictor=OraclePredictor())
    t = 0.0
    eng.submit(reqs2[0], t)
    for _ in range(3):
        eng.step(t); t += 0.1
    for r in reqs2[1:]:
        eng.submit(r, t)
    for _ in range(300):
        if not eng.sched.live:
            break
        eng.step(t); t += 0.1
    for r in reqs2:
        assert ref[r.req_id] == list(r.output_tokens)


def test_engine_rejects_unknown_platform(model_and_params):
    """The Pallas mode comes from the platform the replica's parameters
    live on: 'cpu' interprets, 'tpu' compiles, and anything else is
    refused at construction instead of silently picking a mode."""
    from repro.kernels import interpret_mode

    assert interpret_mode("cpu") is True
    assert interpret_mode("tpu") is False

    class _Gpu:
        platform, id = "gpu", 0

    class _Leaf:                       # a parameter that lives on a GPU
        def devices(self):
            return {_Gpu()}

    _, model, _ = model_and_params
    for backend in ("dense", "paged"):
        with pytest.raises(ValueError, match="'gpu'"):
            ServingEngine(model, {"w": _Leaf()}, EngineConfig(
                max_slots=2, max_seq_len=32, kv_backend=backend))


def test_engine_interpret_mode_follows_params_device(model_and_params):
    _, model, params = model_and_params
    eng = ServingEngine(model, params, EngineConfig(
        max_slots=2, max_seq_len=32, kv_backend="paged"))
    assert eng.kv.device.platform == "cpu" and eng.kv.interpret is True
    assert eng.device == f"cpu:{eng.kv.device.id}"
