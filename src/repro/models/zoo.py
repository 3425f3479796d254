"""Model facade: one object per architecture binding config → params,
entries, caches, input specs, and FaaSLight metadata.

``Model.entries()`` is the Application Entry Recognition surface (DESIGN.md
§4.1): each entry is a jittable function plus abstract input specs, which is
exactly what the Program Analyzer traces and what the dry-run lowers.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import transformer as tf
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xlstm_mod
from repro.models.spec import (
    ParamSpec,
    abstract_params,
    access_annotations,
    init_params,
    logical_axes,
)
from repro.utils.tree import flatten_with_paths, tree_num_params

WHISPER_DECODE_ENC_LEN = 1500  # 30 s audio window for decode-mode serving


@dataclass(frozen=True)
class CacheLeaf:
    shape: tuple
    dtype: Any
    axes: tuple


@dataclass(frozen=True)
class EntryPoint:
    """(name, fn, abstract args) — the FaaSLight 'serverless function'."""

    name: str
    fn: Callable  # fn(params, *args)
    args: tuple  # abstract arg trees (ShapeDtypeStructs)
    arg_axes: tuple  # matching logical-axes trees
    kind: str  # train | prefill | decode


class Model:
    def __init__(self, cfg: ModelConfig):
        cfg.validate()
        self.cfg = cfg
        self.spec = _with_param_dtype(tf.stack_spec(cfg), jnp.dtype(cfg.param_dtype))
        self.layout = tf.stack_layout(cfg)

    # -- params ------------------------------------------------------------
    def init(self, key: jax.Array, dtype=None) -> dict:
        return init_params(self.spec, key, dtype_override=dtype)

    def abstract(self, dtype=None) -> dict:
        return abstract_params(self.spec, dtype_override=dtype)

    def logical_axes(self) -> dict:
        return logical_axes(self.spec)

    def access(self) -> dict[str, str]:
        return access_annotations(self.spec)

    def axes(self) -> dict[str, tuple]:
        """dotted-path -> logical axes tuple (ParamSpec.axes)."""
        return {p: s.axes for p, s in flatten_with_paths(self.spec)}

    def num_params(self) -> int:
        return tree_num_params(self.abstract())

    def active_params(self) -> int:
        """Parameters touched per token (MoE experts scaled by top_k/E)."""
        total = 0
        access = self.access()
        m = self.cfg.moe
        for path, leaf in flatten_with_paths(self.abstract()):
            n = int(np.prod(leaf.shape))
            if access.get(path) == "routed" and m is not None:
                n = int(n * m.top_k / m.num_experts)
            total += n
        return total

    # -- forward fns ---------------------------------------------------------
    def loss_fn(self, params, batch):
        return tf.loss_fn(self.cfg, params, batch)

    def prefill(self, params, batch):
        return tf.prefill(self.cfg, params, batch)

    def decode_step(self, params, caches, batch):
        return tf.decode_step(self.cfg, params, caches, batch)

    def decode_step_masked(self, params, caches, batch):
        """One decode step over a scheduler's slot batch — requires
        ``batch["active"]`` (the continuous-batching entry, DESIGN.md §9).

        ``active`` gates exactly one thing: usage-mask collection
        (``moe_forward(usage_rows=...)``), so a free/completed slot
        decoding a pad token can never fault a cold expert in. Inactive
        rows otherwise compute garbage that is never read — their logits
        are ignored and their cache rows are rebuilt from zeros at the
        next admission (``scheduler._graft_slot_cache``), so there is no
        per-leaf select on the request path (an earlier variant froze
        inactive rows with a full-cache ``where`` merge; that copy cost
        more per step than the batching saved)."""
        if "active" not in batch:
            raise ValueError("decode_step_masked needs batch['active'] (B,) bool")
        return tf.decode_step(self.cfg, params, caches, batch)

    # -- caches --------------------------------------------------------------
    def _block_cache_template(self, kind: str, B: int, S_max: int, multimodal: bool) -> dict:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        Hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        out: dict[str, CacheLeaf] = {}
        if kind in ("self", "local", "global", "attn"):
            if cfg.mla is not None:
                m = cfg.mla
                out["ckv"] = CacheLeaf((B, S_max, m.kv_lora_rank), dt, ("batch", "kv_seq", None))
                out["kr"] = CacheLeaf((B, S_max, m.qk_rope_head_dim), dt, ("batch", "kv_seq", None))
            else:
                window = tf._kind_window(cfg, kind)
                Skv = min(S_max, window) if window else S_max
                out["k"] = CacheLeaf((B, Skv, Hkv, hd), dt, ("batch", "kv_seq", "kv_heads", None))
                out["v"] = CacheLeaf((B, Skv, Hkv, hd), dt, ("batch", "kv_seq", "kv_heads", None))
            if cfg.encdec is not None and multimodal:
                # audio-serving caches only; text-only decode must match a
                # text-only prefill (no cross-attn state at all)
                T = WHISPER_DECODE_ENC_LEN
                out["xk"] = CacheLeaf((B, T, Hkv, hd), dt, ("batch", None, "kv_heads", None))
                out["xv"] = CacheLeaf((B, T, Hkv, hd), dt, ("batch", None, "kv_heads", None))
        elif kind == "cross":
            if multimodal:
                T = cfg.vlm.num_image_tokens
                out["xk"] = CacheLeaf((B, T, Hkv, hd), dt, ("batch", None, "kv_heads", None))
                out["xv"] = CacheLeaf((B, T, Hkv, hd), dt, ("batch", None, "kv_heads", None))
        elif kind == "rec":
            w = cfg.recurrent.lru_width or cfg.d_model
            cw = cfg.recurrent.conv_width
            out["conv"] = CacheLeaf((B, cw - 1, w), dt, ("batch", None, "ffn"))
            out["lru"] = CacheLeaf((B, w), dt, ("batch", "ffn"))
        elif kind == "m":
            xc = cfg.xlstm
            di = int(cfg.d_model * xc.proj_factor_m)
            H = cfg.num_heads
            hd_i = di // H
            out["C"] = CacheLeaf((B, H, hd_i, hd_i), jnp.float32, ("batch", "heads", None, None))
            out["n"] = CacheLeaf((B, H, hd_i), jnp.float32, ("batch", "heads", None))
            out["m"] = CacheLeaf((B, H), jnp.float32, ("batch", "heads"))
            out["conv"] = CacheLeaf((B, xc.conv_width - 1, di), dt, ("batch", None, "ffn"))
        elif kind == "s":
            H = cfg.num_heads
            hd_s = cfg.d_model // H
            for k in ("c", "n", "h", "m"):
                out[k] = CacheLeaf((B, H, hd_s), jnp.float32, ("batch", "heads", None))
        return out

    def cache_template(self, B: int, S_max: int, multimodal: bool = True) -> dict:
        lay = self.layout
        tpl: dict[str, Any] = {}
        if lay.lead_kinds:
            tpl["lead"] = {
                f"b{i}": self._block_cache_template(k, B, S_max, multimodal)
                for i, k in enumerate(lay.lead_kinds)
            }
        if lay.n_groups:
            unit = {
                f"u{j}": self._block_cache_template(k, B, S_max, multimodal)
                for j, k in enumerate(lay.unit_kinds)
            }

            def _stack(leaf: CacheLeaf) -> CacheLeaf:
                return CacheLeaf((lay.n_groups,) + leaf.shape, leaf.dtype, ("layers",) + leaf.axes)

            tpl["groups"] = jax.tree.map(_stack, unit, is_leaf=lambda x: isinstance(x, CacheLeaf))
        if lay.tail_kinds:
            tpl["tail"] = {
                f"b{i}": self._block_cache_template(k, B, S_max, multimodal)
                for i, k in enumerate(lay.tail_kinds)
            }
        return tpl

    def abstract_cache(self, B: int, S_max: int, multimodal: bool = True):
        tpl = self.cache_template(B, S_max, multimodal)
        return jax.tree.map(
            lambda c: jax.ShapeDtypeStruct(c.shape, c.dtype), tpl, is_leaf=lambda x: isinstance(x, CacheLeaf)
        )

    def cache_axes(self, B: int, S_max: int, multimodal: bool = True):
        tpl = self.cache_template(B, S_max, multimodal)
        return jax.tree.map(lambda c: c.axes, tpl, is_leaf=lambda x: isinstance(x, CacheLeaf))

    def init_cache(self, B: int, S_max: int, multimodal: bool = True):
        tpl = self.cache_template(B, S_max, multimodal)
        return jax.tree.map(
            lambda c: jnp.zeros(c.shape, c.dtype), tpl, is_leaf=lambda x: isinstance(x, CacheLeaf)
        )

    # -- batches -------------------------------------------------------------
    def _extra_batch_specs(self, B: int, S: int, *, multimodal: bool) -> tuple[dict, dict]:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        specs, axes = {}, {}
        if cfg.encdec is not None:
            specs["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
            axes["frames"] = ("batch", "seq", "embed")
        if cfg.vlm is not None and multimodal:
            specs["image_embeds"] = jax.ShapeDtypeStruct((B, cfg.vlm.num_image_tokens, cfg.vlm.vision_dim), dt)
            axes["image_embeds"] = ("batch", None, None)
        return specs, axes

    def train_batch_spec(self, B: int, S: int, *, multimodal: bool = True) -> tuple[dict, dict]:
        i32 = jnp.int32
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
        axes = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}
        e_s, e_a = self._extra_batch_specs(B, S, multimodal=multimodal)
        specs.update(e_s)
        axes.update(e_a)
        return specs, axes

    def prefill_batch_spec(self, B: int, S: int, *, multimodal: bool = True) -> tuple[dict, dict]:
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        axes = {"tokens": ("batch", "seq")}
        e_s, e_a = self._extra_batch_specs(B, S, multimodal=multimodal)
        specs.update(e_s)
        axes.update(e_a)
        return specs, axes

    def decode_batch_spec(self, B: int) -> tuple[dict, dict]:
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        }
        axes = {"tokens": ("batch", None), "pos": ("batch",)}
        return specs, axes

    def decode_masked_batch_spec(self, B: int) -> tuple[dict, dict]:
        """decode_batch_spec plus the scheduler's per-slot active mask."""
        specs, axes = self.decode_batch_spec(B)
        specs["active"] = jax.ShapeDtypeStruct((B,), jnp.bool_)
        axes["active"] = ("batch",)
        return specs, axes

    # -- entry registry (Application Entry Recognition) ----------------------
    def entries(self, B: int = 1, S: int = 128, *, multimodal: Optional[bool] = None) -> list[EntryPoint]:
        """All entry points at a given (B, S). ``multimodal=None`` registers
        both modal variants for modal archs (the analyzer needs both)."""
        out = []
        modal_variants: tuple[bool, ...]
        if self.cfg.vlm is not None or self.cfg.encdec is not None:
            modal_variants = (True, False) if multimodal is None else (multimodal,)
        else:
            modal_variants = (True,)
        for mm in modal_variants:
            suffix = "" if mm else "_text_only"
            tb, ta = self.train_batch_spec(B, S, multimodal=mm)
            if not mm:
                tb.pop("frames", None)
                ta.pop("frames", None)
            out.append(EntryPoint(f"train_step{suffix}", self.loss_fn, (tb,), (ta,), "train"))
            pb, pa = self.prefill_batch_spec(B, S, multimodal=mm)
            if not mm:
                pb.pop("frames", None)
                pa.pop("frames", None)
            out.append(EntryPoint(f"prefill{suffix}", self.prefill, (pb,), (pa,), "prefill"))
            cache = self.abstract_cache(B, S, multimodal=mm)
            caxes = self.cache_axes(B, S, multimodal=mm)
            db, da = self.decode_batch_spec(B)
            out.append(EntryPoint(f"decode_step{suffix}", self.decode_step, (cache, db), (caxes, da), "decode"))
        return out

    def input_specs(self, shape: ShapeSpec, *, multimodal: bool = True) -> EntryPoint:
        """The single (arch × shape) dry-run cell entry."""
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            tb, ta = self.train_batch_spec(B, S, multimodal=multimodal)
            return EntryPoint("train_step", self.loss_fn, (tb,), (ta,), "train")
        if shape.kind == "prefill":
            pb, pa = self.prefill_batch_spec(B, S, multimodal=multimodal)
            return EntryPoint("prefill", self.prefill, (pb,), (pa,), "prefill")
        cache = self.abstract_cache(B, S, multimodal=multimodal)
        caxes = self.cache_axes(B, S, multimodal=multimodal)
        db, da = self.decode_batch_spec(B)
        return EntryPoint("decode_step", self.decode_step, (cache, db), (caxes, da), "decode")


def _with_param_dtype(spec: Any, dtype) -> Any:
    """Every floating ParamSpec at rest in ``dtype``."""
    def cast(s: ParamSpec) -> ParamSpec:
        if jnp.issubdtype(s.dtype, jnp.floating) and s.dtype != dtype:
            return dataclasses.replace(s, dtype=dtype)
        return s

    return jax.tree.map(cast, spec, is_leaf=lambda x: isinstance(x, ParamSpec))


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
