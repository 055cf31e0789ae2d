"""The plain reference against the system's served tokens, on the CPU at
toy widths, for both configurations: every token the system served
(first token from chunked prefill into the paged pool, the rest from the
fused paged decode step and its greedy sampler) is the reference's first
choice at its position, within a stated gap of the reference's logits."""
import pytest

from bench_chip_tiny import run_tiny, tiny_cell

CELLS = [("granite-3-8b", "sharegpt-1.28rps"),
         ("internvl2-2b-lm", "mixed-2.8rps")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_float32_program_matches_the_reference(config, traffic):
    # float32 weights, compute and KV on both sides: only the order of
    # summation differs, so a served token's reference logit lies at most
    # 1e-4 below the best (logits here spread by about 0.15)
    cell = tiny_cell(config, traffic, param_dtype="float32",
                     compute_dtype="float32")
    cell.config["model"]["kv_dtype"] = "float32"
    cell.config["check"]["logit_gap_limit"] = 1e-4
    out = run_tiny(cell)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("config,traffic", CELLS)
def test_bfloat16_program_within_the_tiny_limit(config, traffic):
    out = run_tiny(tiny_cell(config, traffic), seed=2 ** 35 + 11)
    gap = out["compared"]["logit_gap"]
    assert out["correct"], out["compared"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert out["compared"]["compiles_serving"]["value"] == 0
    assert out["compared"]["wrong_token_counts"]["value"] == 0
