"""The plain float32 reference against the program's own prefill and
decode, at toy widths on the CPU, with float32 weights and activations so
that the two differ only by the order of float32 sums."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import weights  # noqa: E402
from bench.arch import program_config  # noqa: E402
from bench.families.mistral import Arch  # noqa: E402
from bench.reference import mistral as reference  # noqa: E402

BASE = dict(name="toy", layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=96,
            vocab=256, rope_theta=1e4, norm_eps=1e-5, window=None, experts=0, top_k=0,
            dtype="float32")
ARCHS = {
    "dense": Arch(**BASE),
    "moe": Arch(**{**BASE, "experts": 4, "top_k": 2}),
    "dense_window": Arch(**{**BASE, "window": 14}),  # decode wraps the rolling cache
}
PROMPT, STEPS, SEED = 12, 6, 2**31 + 5
# float32 sums in another order: about 1e-6 of a logit; 1e-4 leaves room
# and is still far below any change in the arithmetic
RTOL = 1e-4


@pytest.mark.parametrize("kind", sorted(ARCHS))
def test_reference_matches_program_prefill_and_decode(kind):
    from repro.models.zoo import build_model

    arch = ARCHS[kind]
    model = build_model(program_config(arch))
    params = weights.program_params(arch, model, SEED)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, arch.vocab, PROMPT, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        logits, caches = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(prompt)[None]})
        got = [np.asarray(logits[0])]
        cache = model.init_cache(1, PROMPT + STEPS, multimodal=False)
        cache = jax.tree.map(lambda b, s: b.at[tuple(slice(0, d) for d in s.shape)].set(s),
                             cache, jax.tree.map(jnp.asarray, _strip(caches)))
        toks = [int(np.argmax(got[-1]))]
        step = jax.jit(model.decode_step)
        for i in range(STEPS - 1):
            batch = {"tokens": jnp.asarray([[toks[-1]]], jnp.int32),
                     "pos": jnp.asarray([PROMPT + i], jnp.int32)}
            lg, cache = step(params, cache, batch)
            cache = _strip(cache)
            got.append(np.asarray(lg[0]))
            toks.append(int(np.argmax(got[-1])))
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    want = reference.logits(arch, SEED, [seq], [PROMPT - 1], pad_to=PROMPT + STEPS)[0]
    assert want.shape == (STEPS, arch.vocab)
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=RTOL * scale)


def _strip(tree):
    if isinstance(tree, dict):
        return {k: _strip(v) for k, v in tree.items() if k != "moe_usage"}
    return tree


def test_one_call_and_leaf_by_leaf_weights_are_identical():
    from repro.models.zoo import build_model
    from repro.utils.tree import flatten_with_paths

    arch = Arch(**{**BASE, "experts": 4, "top_k": 2, "dtype": "bfloat16"})
    old = weights.CHUNK
    weights.CHUNK = 1 << 12  # force the blocked path on toy leaves
    try:
        params = dict(flatten_with_paths(
            weights.program_params(arch, build_model(program_config(arch)), SEED)))
        for path, name in [("embed", "embed"), ("groups.u0.moe.w_up", "w_up"),
                           ("groups.u0.attn.wq", "wq"), ("groups.u0.norm2", "norm2")]:
            np.testing.assert_array_equal(np.asarray(params[path]),
                                          np.asarray(weights.leaf(arch, SEED, name)))
    finally:
        weights.CHUNK = old


def test_fp8_control_moves_the_logits():
    arch = ARCHS["moe"]
    seq = np.arange(PROMPT, dtype=np.int32) % arch.vocab
    exact = reference.logits(arch, SEED, [seq], [0], pad_to=PROMPT)[0]
    low = reference.logits(arch, SEED, [seq], [0], pad_to=PROMPT, precision="fp8")[0]
    err = np.abs(low - exact).max() / np.abs(exact).max()
    assert 1e-3 < err < 0.5
