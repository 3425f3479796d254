"""The serving launcher's depth cut, argument checks and artifact writer."""

import jax
import numpy as np
import pytest

from repro.checkpoint import tensorstore_lite as tsl
from repro.configs import get_config
from repro.launch import serve
from repro.models.zoo import build_model


def test_cut_depth_keeps_every_width():
    full = get_config("mixtral-8x22b")
    cut = serve.cut_depth(full, 1)
    assert cut.num_layers == 1 and cut.name == "mixtral-8x22b-1L"
    for field in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "sliding_window", "moe"):
        assert getattr(cut, field) == getattr(full, field), field


@pytest.mark.parametrize("arch,layers", [
    ("mixtral-8x22b", 0),
    ("mixtral-8x22b", 57),
    ("recurrentgemma-9b", 2),   # ("rec", "rec", "attn"): no attention layer left
    ("deepseek-v2-lite-16b", 1),  # only the leading dense layer
])
def test_cut_depth_refuses_partial_models(arch, layers):
    with pytest.raises(ValueError):
        serve.cut_depth(get_config(arch), layers)


@pytest.mark.parametrize("argv", [
    ["--arch", "mixtral-8x22b", "--reduced", "--layers", "1"],
    ["--arch", "recurrentgemma-9b", "--layers", "2"],
])
def test_parse_args_rejects_bad_cuts(argv, capsys):
    with pytest.raises(SystemExit) as e:
        serve.parse_args(argv)
    assert e.value.code == 2
    assert "--layers" in capsys.readouterr().err


def test_load_config_serves_weights_in_activation_dtype():
    args, _ = serve.parse_args(["--arch", "mixtral-8x22b", "--layers", "1"])
    cfg = serve.load_config(args)
    model = build_model(cfg)
    dtypes = {leaf.dtype for leaf in jax.tree.leaves(model.abstract())}
    assert dtypes == {np.dtype(cfg.dtype)}
    assert cfg.collect_moe_usage


def test_monolithic_artifact_keeps_optimizer_state_on_host(tmp_path):
    """before/after1 bundles ship zero AdamW moments, made on the host."""
    args, _ = serve.parse_args(["--arch", "mixtral-8x22b", "--reduced", "--mode", "before"])
    model = build_model(serve.load_config(args))
    params = serve.init_weights(model, seed=0)
    serve.write_artifact(model, params, None, str(tmp_path), "before")
    flat = tsl.read_bundle(str(tmp_path / "before"))
    moments = [k for k in flat if k.startswith("opt_state.")]
    assert moments and all(flat[k].dtype == np.float32 and not flat[k].any() for k in moments)
    n_params = len(jax.tree.leaves(params))
    assert len(moments) == 2 * n_params


def test_main_one_shot_reduced(tmp_path, capsys):
    rc = serve.main(["--arch", "mixtral-8x22b", "--reduced", "--artifact-dir", str(tmp_path),
                     "--prompt-len", "8", "--gen-steps", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "[serve] generated (2, 3)" in out and "[serve] cold start (after2)" in out
