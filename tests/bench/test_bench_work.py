"""The benchmark's operation and byte counts against shapes worked by hand."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import work  # noqa: E402
from bench.families.mistral import Arch  # noqa: E402

# 2 layers, hidden 8, 2 query heads and 1 key/value head of 4, MLP 16, vocab 32
DENSE = Arch(name="hand", layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
             vocab=32, rope_theta=1e4, norm_eps=1e-5, window=None, experts=0, top_k=0,
             dtype="bfloat16")
MOE = Arch(**{**DENSE.__dict__, "experts": 4, "top_k": 2, "window": 3})

ATTN = 8 * (2 * 4 + 2 * 1 * 4) + 2 * 4 * 8          # wq, wk, wv, wo = 192 parameters
KV_ROW = 2 * 2 * 2 * 1 * 4                          # bytes of K and V of one position: 32


def test_dense_prefill_counts():
    # one prompt of 3 tokens: per token 2*ATTN + SwiGLU 2*3*8*16; scores 1+2+3
    per_layer = 3 * (2 * ATTN + 2 * 3 * 8 * 16) + 4 * 2 * 4 * (1 + 2 + 3)
    flops = 2 * per_layer + 2 * 8 * 32                # logits of the last token only
    weights = 2 * (ATTN + 2 * 8 + 3 * 8 * 16) + 8 + 32 * 8
    nbytes = 2 * weights + 2 * 3 * 8 + 3 * KV_ROW      # weights, embedded rows, K/V written
    assert work.prefill(DENSE, [3]) == (flops, nbytes)


def test_dense_decode_counts():
    # two sequences attending over 5 and 2 positions
    per_layer = 2 * (2 * ATTN + 2 * 3 * 8 * 16) + 4 * 2 * 4 * (5 + 2)
    flops = 2 * per_layer + 2 * 2 * 8 * 32
    weights = 2 * (ATTN + 2 * 8 + 3 * 8 * 16) + 8 + 32 * 8
    nbytes = 2 * weights + 2 * 2 * 8 + (5 + 2 + 2) * KV_ROW
    assert work.decode(DENSE, [5, 2]) == (flops, nbytes)


def test_moe_decode_counts_routed_experts_and_window():
    # top-2 of 4 experts per token; window 3 caps the 5 positions at 3;
    # 3 (layer, expert) pairs routed in the step
    mlp = 2 * (8 * 4 + 2 * 3 * 8 * 16)
    per_layer = 2 * (2 * ATTN + mlp) + 4 * 2 * 4 * (3 + 2)
    flops = 2 * per_layer + 2 * 2 * 8 * 32
    weights = 2 * (ATTN + 2 * 8 + 8 * 4) + 3 * 3 * 8 * 16 + 8 + 32 * 8
    nbytes = 2 * weights + 2 * 2 * 8 + (3 + 2 + 2) * KV_ROW
    assert work.decode(MOE, [5, 2], experts_read=3) == (flops, nbytes)


def test_routed_experts_counts_distinct_layer_expert_pairs():
    keys = ["groups.u0.moe.w_gate#l0e3", "groups.u0.moe.w_up#l0e3", "groups.u0.moe.w_down#l0e3",
            "groups.u0.moe.w_gate#l1e3", "lead.b0.moe.w_gate#e5", "embed#rg2"]
    assert work.routed_experts(keys) == 3
