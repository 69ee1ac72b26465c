"""The model-FLOP count against a count by hand."""
import json

from bench import flops
from bench_tiny import ROOT


def load(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_gpt3_xl_by_hand():
    # per layer: q, k, v, o 4 x 2048^2; SwiGLU 3 x 2048 x 8192; 5 layers;
    # head 2048 x 50257
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    params = 5 * per_layer + 2048 * 50257
    assert params == 438_470_656
    per_token = 6 * params + 12 * 5 * 2048 * 2048
    assert flops.flops_per_token(load("gpt3-xl"), 2048) == per_token
    assert flops.flops_per_step(load("gpt3-xl"), 8, 2048) == \
        8 * 2048 * per_token == 47_226_587_971_584


def test_gpt2_1_5b_widths_by_hand():
    """GPT-2 1.5B's widths (25 heads of 64, d_ff 6400) at 8 layers, the depth
    one v5e holds: heads times head size need not be a power of two."""
    cfg = load("gpt2-1.5b")
    assert (cfg["d_model"], cfg["num_heads"], cfg["head_dim"], cfg["d_ff"],
            cfg["num_layers"]) == (1600, 25, 64, 6400, 8)
    per_layer = 4 * 1600 * 1600 + 3 * 1600 * 6400
    params = 8 * per_layer + 1600 * 50257
    assert flops.matmul_params(cfg) == params == 408_091_200
    per_token = 6 * params + 12 * 8 * 2048 * 1600
    assert flops.flops_per_token(cfg, 2048) == per_token
    assert flops.flops_per_step(cfg, 8, 2048) == 45_270_958_080_000
