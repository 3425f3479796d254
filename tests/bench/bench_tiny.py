"""A toy cell for the CPU tests: the harness's whole run, set-up, window
and check, at widths a test can hold.

Its limits were read from the toy program and its fp8 control on seeds
0-7 each, with Zipfian prompt tokens (PERF.md). Dense: the widest gap,
program at most 0.0109, control at least 0.0911. With experts, like
mixtral: the share of served tokens more than two bfloat16 steps below
the best, program 0 on every seed, control at least 0.0759."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


DENSE_CHECK = {"tokens_compared": 40, "logit_gap": 0.05}
MOE_CHECK = {"tokens_compared": 40, "beyond_2ulp_share": 0.035}


def conf(experts: int) -> dict:
    return {"name": f"toy-{experts}e", "reference": "bench/reference/mistral.py",
            "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
            "num_hidden_layers": 2, "num_local_experts": experts,
            "num_experts_per_tok": 2 if experts else 0, "vocab_size": 1024,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "sliding_window": None,
            "torch_dtype": "bfloat16",
            "plan": {"policy": "stats", "resident_experts": 1, "hot_vocab": 0.25},
            "residency": "full",
            "check": MOE_CHECK if experts else DENSE_CHECK}


MIX = {"start": "warm", "slots": 4, "rate_rps": 3.0, "prompt_lens": [8, 16],
       "prompt_weights": [0.6, 0.4], "prompt_zipf_s": 1.1, "output_median": 8, "output_sigma": 0.5,
       "output_min": 4, "output_max": 16, "block": 4, "warm_group": 2}


def run(tmp_path, experts: int, seed: int, *, control: bool = False, start: str = "warm",
        seconds: float = 4.0) -> dict:
    return harness.run_cell({"name": "toy", "chips": 1}, conf(experts), {**MIX, "start": start},
                            seed=seed, seconds=seconds, trace=False,
                            e2e_names=[("tokens_per_s", "tokens/s")], per_layer=[], t_start=0.0,
                            control=control, workdir=tmp_path, log=lambda s: None)
