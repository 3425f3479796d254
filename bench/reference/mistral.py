"""Plain float32 reference of a Mistral-family decoder.

Written from the published description (Mistral 7B, arXiv:2310.06825;
Mixtral, arXiv:2401.04088): token embedding, then per layer a pre-norm
RMSNorm, grouped-query attention with rotary positions (half-split
rotation, theta from the configuration), causal and, where the
configuration sets one, windowed to the last ``sliding_window``
positions, a residual add, a pre-norm RMSNorm and either a SwiGLU MLP or
a top-k mixture of SwiGLU experts (softmax router, the k largest
probabilities renormalised to sum to 1), a residual add; a final RMSNorm
and an untied output head.

One departure from the published model, kept so that the two can be
compared at all: the embedding is multiplied by sqrt(hidden_size), as the
program under test does for every family.

Nothing of the program is imported. Weights come from ``bench.weights``
by name and seed, one leaf at a time, and are upcast to float32 layer by
layer, so the reference fits beside nothing else on one chip. Every
matrix product runs at ``highest`` precision. ``precision="fp8"`` is the
control: every matrix product's operands rounded to float8 (e4m3, scaled
per row), the precision one step below the bfloat16 the configuration
serves in.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.families.mistral import Arch

NEG = -1e30
Q_CHUNK = 512  # queries per attention block, so scores never hold (P, P) for all heads
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``'s
    complement (per row of an activation, per output column of a weight)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """a (..., k) @ b (k, n) in float32, or with fp8-rounded operands."""
    if fp8:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (P, H, hd); rotate the two halves of each head (GPT-NeoX layout)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("arch", "fp8"))
def _attention_block(x, norm1, wq, wk, wv, wo, *, arch: Arch, fp8: bool):
    P = x.shape[0]
    H, Hkv, hd = arch.heads, arch.kv_heads, arch.head_dim
    G = H // Hkv
    h = _rmsnorm(x, norm1, arch.norm_eps)
    pos = jnp.arange(P)
    q = _rope(_mm(h, wq, fp8).reshape(P, H, hd), pos, arch.rope_theta)
    k = _rope(_mm(h, wk, fp8).reshape(P, Hkv, hd), pos, arch.rope_theta)
    v = _mm(h, wv, fp8).reshape(P, Hkv, hd)
    kh = jnp.repeat(k, G, axis=1)  # query head i reads kv head i // G
    vh = jnp.repeat(v, G, axis=1)
    if fp8:
        kh, vh = _q8(kh, -1), _q8(vh, 0)
    outs = []
    for c0 in range(0, P, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        if fp8:
            qc = _q8(qc, -1)
        s = jnp.einsum("qhd,khd->hqk", qc, kh) / math.sqrt(hd)
        qp = (c0 + jnp.arange(qc.shape[0]))[:, None]
        kp = pos[None, :]
        ok = kp <= qp
        if arch.window is not None:
            ok = ok & (qp - kp < arch.window)
        s = jnp.where(ok[None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vh))
    o = jnp.concatenate(outs, axis=0).reshape(P, H * hd)
    return x + _mm(o, wo, fp8)


@partial(jax.jit, static_argnames=("arch", "fp8"))
def _mlp_block(x, norm2, wg, wu, wd, *, arch: Arch, fp8: bool):
    h = _rmsnorm(x, norm2, arch.norm_eps)
    return x + _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


@partial(jax.jit, static_argnames=("arch", "fp8"))
def _route(x, norm2, router, *, arch: Arch, fp8: bool):
    """-> (normed input, (P, E) gate weights: renormalised top-k, else 0,
    (P,) router margin: the k-th largest probability minus the next)."""
    h = _rmsnorm(x, norm2, arch.norm_eps)
    probs = jax.nn.softmax(_mm(h, router, fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, arch.top_k + 1)
    margin = top[:, -2] - top[:, -1]  # how near the last chosen expert came to losing its place
    top, idx = top[:, :-1], idx[:, :-1]
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(probs.shape[0])[:, None], idx].set(top)
    return h, gates, margin


@partial(jax.jit, static_argnames=("fp8",))
def _expert(y, h, gate, wg, wu, wd, *, fp8: bool):
    out = _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)
    return y + gate[:, None] * out


@partial(jax.jit, static_argnames=("arch", "fp8"))
def _head(x, final_norm, head, *, arch: Arch, fp8: bool):
    return _mm(_rmsnorm(x, final_norm, arch.norm_eps), head.T, fp8)


def logits(arch: Arch, seed: int, seqs: list[np.ndarray], first: list[int],
           *, pad_to: int, precision: str = "fp32", margins: list | None = None) -> list[np.ndarray]:
    """Reference logits of each token sequence at positions ``first[i]``
    onward: ``out[i][j]`` is the distribution over the token that follows
    ``seqs[i][first[i] + j]``. Every sequence is padded at the end to
    ``pad_to`` positions, so each block compiles once for a traffic mix; a
    causal model never reads the padding at the positions returned. For a
    model with experts, ``margins`` (a list) receives per sequence the
    router margin at the same positions, the least over the layers: how
    near a token came to being routed to another expert."""
    fp8 = {"fp32": False, "fp8": True}[precision]
    P = pad_to
    if max(len(s) for s in seqs) > P:
        raise ValueError(f"a sequence is longer than pad_to={P}")
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        emb = W.leaf(arch, seed, "embed")
        scale = f32(math.sqrt(arch.d_model))
        xs = []
        for s in seqs:
            t = np.zeros(P, np.int32)
            t[: len(s)] = s
            xs.append(jnp.take(emb, jnp.asarray(t), axis=0).astype(f32) * scale)
        del emb
        names = ["norm1", "wq", "wk", "wv", "wo", "norm2"]
        names += ["router", "w_gate", "w_up", "w_down"] if arch.moe else ["w_gate", "w_up", "w_down"]
        for layer in range(arch.layers):
            lw = {}
            for n in names:
                stacked = W.leaf(arch, seed, n)
                lw[n] = stacked[layer]  # bf16 until used
                del stacked
            att = [lw[n].astype(f32) for n in ("norm1", "wq", "wk", "wv", "wo")]
            xs = [_attention_block(x, *att, arch=arch, fp8=fp8) for x in xs]
            del att
            n2 = lw["norm2"].astype(f32)
            if arch.moe:
                routed = [_route(x, n2, lw["router"].astype(f32), arch=arch, fp8=fp8) for x in xs]
                ms = [m for _, _, m in routed]
                least = ms if layer == 0 else [jnp.minimum(a, b) for a, b in zip(least, ms)]
                ys = list(xs)
                for e in range(arch.experts):
                    ew = [lw[n][e].astype(f32) for n in ("w_gate", "w_up", "w_down")]
                    ys = [_expert(y, h, g[:, e], *ew, fp8=fp8) for y, (h, g, _) in zip(ys, routed)]
                    del ew
                xs = ys
                del routed
            else:
                mw = [lw[n].astype(f32) for n in ("w_gate", "w_up", "w_down")]
                xs = [_mlp_block(x, n2, *mw, arch=arch, fp8=fp8) for x in xs]
                del mw
            del lw
        fn = W.leaf(arch, seed, "final_norm").astype(f32)
        head = W.leaf(arch, seed, "head").astype(f32)
        out = []
        for x, s, f in zip(xs, seqs, first):
            out.append(np.asarray(_head(x, fn, head, arch=arch, fp8=fp8)[f: len(s)]))
        if margins is not None and arch.moe:
            margins += [np.asarray(m[f: len(s)]) for m, s, f in zip(least, seqs, first)]
        return out
