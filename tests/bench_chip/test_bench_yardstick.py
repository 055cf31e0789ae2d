"""The benchmark's yardstick on the CPU: the dense architecture's
operation and byte counts against hand-worked granite-3-8b numbers, the
table of peaks, the seeded weights, the traffic generator and the latency
readers."""
import zlib

import numpy as np
import pytest

import bench_chip_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmarks.chip import harness, peaks, traffic, weights

DENSE = harness.architecture("dense_gqa")

# granite-3-8b as served on one chip: 24 of 40 layers, published widths
GRANITE = dict(num_layers=24, d_model=4096, num_heads=32, num_kv_heads=8,
               head_dim=128, d_ff=12800, vocab_size=49155,
               tie_embeddings=True, act="swiglu")


def test_granite_weights_and_kv_by_hand():
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, three FFN
    # matrices 4096x12800
    per_layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 12800
    assert DENSE.layer_matmul_params(GRANITE) == per_layer == 199_229_440
    # 24 layers with their two norms, the final norm, the tied embedding
    assert DENSE.weight_bytes(GRANITE) == 2 * (
        24 * (per_layer + 2 * 4096) + 4096 + 49155 * 4096) == 9_966_092_288
    # K and V, 24 layers, 8 heads of 128, bf16
    assert DENSE.kv_bytes_per_token(GRANITE) == 98_304


def test_granite_decode_step_by_hand():
    # 16 lanes whose contexts sum to 16 000 tokens
    w = DENSE.decode_step(GRANITE, 16, 16_000)
    matmul = 16 * 2 * (24 * 199_229_440 + 4096 * 49155)
    attn = 16_000 * 4 * 24 * 32 * 128
    assert w.flops == matmul + attn
    assert w.bytes == 9_966_092_288 + 16_000 * 98_304
    peak = peaks.peak_of("TPU v5 lite")
    # weight read dominates: bytes / 819 GB/s, not FLOPs / 197 TFLOP/s
    assert w.least_seconds(peak) == pytest.approx(w.bytes / 819e9)
    assert 0.0135 < w.least_seconds(peak) < 0.0141


def test_granite_prefill_chunk_by_hand():
    # the second 256-token chunk of a prompt: attends to 256 cached
    # tokens and causally to itself
    w = DENSE.prefill_chunk(GRANITE, 256, 256)
    pairs = 256 * 256 + 256 * 257 // 2
    assert w.flops == (256 * 2 * 24 * 199_229_440 + 2 * 4096 * 49155
                       + pairs * 4 * 24 * 32 * 128)
    assert w.bytes == 9_966_092_288 + 512 * 98_304


def test_prefill_of_one_token_matches_a_decode_lane():
    one = DENSE.prefill_chunk(GRANITE, 99, 1)
    step = DENSE.decode_step(GRANITE, 1, 100)
    assert one.flops == step.flops and one.bytes == step.bytes


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_of("TPU v9 imaginary")
    assert peaks.peak_of("TPU v5 lite").bf16_flops == 197e12


SMALL = dict(num_layers=3, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, d_ff=96, vocab_size=130, tie_embeddings=False)


def small_params(seed=2 ** 40 + 3):
    import jax
    import jax.numpy as jnp
    template = {"embed": jax.ShapeDtypeStruct((130, 64), jnp.bfloat16),
                "lm_head": jax.ShapeDtypeStruct((64, 130), jnp.bfloat16),
                "final_norm": {"scale": jax.ShapeDtypeStruct((64,),
                                                             jnp.bfloat16)},
                "layers": {k.split("/")[0]: {} for k in
                           DENSE.layer_shapes(SMALL)}}
    for name, shape in DENSE.layer_shapes(SMALL).items():
        a, b = name.split("/")
        template["layers"][a][b] = jax.ShapeDtypeStruct((3,) + shape,
                                                        jnp.bfloat16)
    return weights.make_params(template, DENSE, SMALL, seed)


def test_weights_layer_alone_equals_stacked_and_bf16_is_exact():
    import jax.numpy as jnp
    key = weights.base_key(2 ** 40 + 3)
    params = small_params()
    alone = weights.make_leaves(key, DENSE.layer_shapes(SMALL), jnp.float32,
                                2)
    for name, arr in alone.items():
        a, b = name.split("/")
        served = np.asarray(params["layers"][a][b][2].astype(jnp.float32))
        np.testing.assert_array_equal(served, np.asarray(arr))
    glob = weights.make_leaves(key, DENSE.global_shapes(SMALL), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"].astype(jnp.float32)),
        np.asarray(glob["lm_head"]))
    # spread near 1/sqrt(fan_in), and layers differ
    wq = np.asarray(alone["attn/wq"])
    assert 0.5 / 8 < wq.std() < 2 / 8
    assert not np.array_equal(np.asarray(params["layers"]["attn"]["wq"][0]),
                              np.asarray(params["layers"]["attn"]["wq"][1]))


def test_weights_refuse_an_unknown_parameter():
    import jax
    import jax.numpy as jnp
    tpl = {"embed": jax.ShapeDtypeStruct((130, 64), jnp.bfloat16),
           "mystery": jax.ShapeDtypeStruct((4, 4), jnp.bfloat16)}
    with pytest.raises(ValueError, match="mystery"):
        weights.make_params(tpl, DENSE, dict(SMALL, tie_embeddings=True), 1)


# crc32 of each leaf's bfloat16 bytes, recorded before the dense decoder's
# parameter tree moved into archs/dense_gqa.py: the weights' bits did not
# change with the move
SMALL_CRC32 = {"embed": 2511938635, "lm_head": 109725983,
               "layers/attn/wq": 3727025050, "layers/attn/wk": 1661665088,
               "layers/ffn/wo": 1160807935, "final_norm/scale": 1028775320}


def test_weights_match_the_recorded_bits():
    import jax
    params = small_params()
    got = {"/".join(str(k.key) for k in path):
           zlib.crc32(np.asarray(leaf).tobytes())
           for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert {k: got[k] for k in SMALL_CRC32} == SMALL_CRC32


def test_weights_several_stacks_each_slice_its_own_layer():
    """Two stacks, as a model with two kinds of layer has: the slice at
    each index is the layer the stack names there, made alone equal to
    made stacked, a three-axis leaf included, mixed dtypes kept."""
    import jax
    import jax.numpy as jnp
    tree = weights.Tree(
        {"embed": (130, 64)},
        {"mix": weights.Stack(((0, 1), (3, 4)), {"in": (64, 32),
                                                 "norm": (64,)}),
         "moe": weights.Stack((2, 5), {"experts": (4, 64, 16),
                                       "decay": (8,)})})
    arch = type("Arch", (), {"params": staticmethod(lambda sizes: tree)})
    sd = jax.ShapeDtypeStruct
    template = {"embed": sd((130, 64), jnp.bfloat16),
                "mix": {"in": sd((2, 2, 64, 32), jnp.bfloat16),
                        "norm": sd((2, 2, 64), jnp.bfloat16)},
                "moe": {"experts": sd((2, 4, 64, 16), jnp.bfloat16),
                        "decay": sd((2, 8), jnp.float32)}}
    params = weights.make_params(template, arch, {}, 2 ** 36 + 1)
    key = weights.base_key(2 ** 36 + 1)
    for at, idx, layer in (("mix", (1, 0), 3), ("mix", (0, 1), 1),
                           ("moe", (1,), 5)):
        alone = weights.make_leaves(key, tree.stacks[at].leaves,
                                    jnp.float32, layer)
        for name, arr in alone.items():
            stacked = params[at][name][idx]
            assert stacked.dtype == template[at][name].dtype
            np.testing.assert_array_equal(
                np.asarray(stacked.astype(jnp.float32)), np.asarray(arr))
    e = np.asarray(params["moe"]["experts"][0].astype(jnp.float32))
    assert not np.array_equal(e[0], e[1])             # experts differ
    assert 0.5 / 8 < e.std() < 2 / 8                  # fan-in 64


MIX = {"rate": 5.0, "ramp_s": 2.0, "drain_cap_s": 10.0,
       "shape_seed": 3, "max_prompt": 1024, "max_output": 1023,
       "interactive_share": 0.25,
       "shapes": {"alpaca": {"share": 0.5, "in_mu": 3.0, "in_sigma": 0.6,
                             "out_median_lo": 24, "out_median_hi": 160,
                             "out_sigma": 0.45, "out_cap": 512,
                             "n_clusters": 64, "length_noise": 0.25},
                  "sharegpt": {"share": 0.5, "in_mu": 5.1, "in_sigma": 0.9,
                               "out_median_lo": 60, "out_median_hi": 640,
                               "out_sigma": 0.85, "out_cap": 2048,
                               "n_clusters": 64, "length_noise": 0.25}}}


def _sizes(arrivals, measured):
    return sorted((a.spec.prompt_len, a.spec.output_len, a.spec.interactive)
                  for a in arrivals if a.measured == measured)


@pytest.mark.parametrize("seeds", [(1, 2), (5, 2 ** 33 + 1)])
def test_open_loop_seeds_shuffle_one_fixed_set(seeds):
    a, b = (traffic.open_loop(MIX, 30.0, s) for s in seeds)
    assert [x.due for x in a] == [x.due for x in b]        # same instants
    assert _sizes(a, True) == _sizes(b, True)              # same sizes
    assert _sizes(a, False) == _sizes(b, False)
    assert [x.spec for x in a] != [x.spec for x in b]      # other order
    n = sum(x.measured for x in a)
    assert 100 < n < 200                                   # ~5/s for 30 s
    assert all(0 <= x.due < 30 for x in a if x.measured)
    assert all(4 <= x.spec.prompt_len <= 1024 and
               1 <= x.spec.output_len <= 1023 for x in a)


def test_prompt_tokens_stay_in_the_vocabulary():
    rng = traffic.token_rng(9)
    spec = traffic.open_loop(MIX, 5.0, 9)[0].spec
    toks = traffic.prompt_tokens(rng, spec, 130)
    assert len(toks) == spec.prompt_len
    assert min(toks) >= 2 and max(toks) < 130


def test_latency_and_pool_readers_by_hand():
    def req(due, times, measured=True, end="finish"):
        r = harness.Req(None, due, measured, len(times), [5])
        r.first, r.finish, r.end = times[0], times[-1], end
        r.tokens, r.times = [7] * len(times), list(times)
        return r
    reqs = [req(0.0, [0.1, 0.2, 0.3]),          # ttft 100 ms, tpot 100 ms
            req(1.0, [1.3, 1.5]),               # 300 ms, 200 ms
            req(2.0, [2.05, 2.1, 2.15, 2.2]),   # 50 ms, 50 ms
            req(3.0, [3.9, 4.0], measured=False)]
    rec = harness.Record({}, {}, 10.0, (0.0, 10.0), reqs,
                         [(0.0, 300, 2, 0.03), (0.1, 900, 3, 0.03)],
                         3000, 1.0)
    read = {m: harness.reader(m)(rec) for m in
            ("ttft_p50_ms", "tpot_p50_ms", "kv.pool_peak_pct")}
    assert read["ttft_p50_ms"] == pytest.approx(100.0)
    assert read["tpot_p50_ms"] == pytest.approx(100.0)
    assert read["kv.pool_peak_pct"] == pytest.approx(30.0)
