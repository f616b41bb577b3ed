"""Plain float32 forward of the benchmarked MoE decoders: the yardstick.

Imports nothing of the program. This module holds what every family
shares; each family module (``bench/families/<model_type>.py``) builds its
layers from it, following the published layer equations. Shared here: the
pre-norm pieces (RMSNorm, rotate-half RoPE), a softmax router choosing the
top k experts, renormalised where the configuration says so, the MoE block
of SwiGLU experts with, for 2T-Drop cells, the DualSparse semantics the
cell states (each expert's neurons ranked by importance on calibration
activations, the top 1/P of them its major part; per-layer thresholds at
the drop-rate quantiles ``target -+ delta`` of the calibration scores; a
pair above the upper threshold computes the whole expert, between the two
the major part, below the lower one nothing), the LM head and the gaps of
served tokens.

Every matrix product runs at ``Precision.HIGHEST`` in float32 from the
bf16 weights the served program was given. It runs layer by layer over one
sequence at a time, attention in blocks of query rows, the experts one at a
time, so it fits beside the weights. ``quant=True`` is the control: every
product's operands rounded to float8 (e4m3) with a scale per row or output
channel, the precision a later change would be tempted by.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 512           # query rows per attention / LM-head block
BUCKET = 512            # sequences are padded to 512 or 1024 times 1, 1.5,
                        # 2, 3, 4, 6 ...


def fake_fp8(x, axes):
    """Round ``x`` to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(spec, a, b, quant, a_axes, b_axes):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        a, b = fake_fp8(a, a_axes), fake_fp8(b, b_axes)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotate-half RoPE over the last axis; x (T, ..., D), pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)     # (T, D/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def route(h, wg, s, quant=False):
    """Top-k routing: (expert ids (T,K), combine weights, normalised
    scores), softmax over all experts in float32."""
    logits = _dot("td,de->te", h, wg, quant, (1,), (0,))
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, s["top_k"])
    norm = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-20)
    return idx, (norm if s["renorm"] else vals), norm


def moe_layer(h, moe, l, thr, major, *, s, two_t, quant=False):
    """Layer ``l`` of the MoE block over tokens h (T, d): top-k routing,
    2T modes when ``two_t``, and every expert's SwiGLU over the tokens
    routed to it (the whole expert, its major neurons, or nothing)."""
    idx, comb, norm = route(h, moe["wg"][l], s, quant)
    if two_t:
        modes = jnp.where(norm > thr[l, 1], 2, jnp.where(norm > thr[l, 0],
                                                         1, 0))
    else:
        modes = jnp.full(norm.shape, 2)

    def expert(y, e):
        sel = idx == e
        c = jnp.sum(jnp.where(sel, comb, 0.0), -1)                 # (T,)
        m = jnp.max(jnp.where(sel, modes, -1), -1)                 # (T,)
        a = _dot("td,df->tf", h, moe["w1"][l, e], quant, (1,), (0,))
        b = _dot("td,df->tf", h, moe["w3"][l, e], quant, (1,), (0,))
        hid = jax.nn.silu(a) * b
        keep = m[:, None] == 2
        if two_t:
            keep = keep | ((m[:, None] == 1) & major[l, e][None, :])
        out = _dot("tf,fd->td", hid * keep, moe["w2"][l, e], quant, (1,),
                   (0,))
        return y + c[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros(h.shape, jnp.float32),
                        jnp.arange(s["experts"]))
    return y


def _head(final_norm, lm_head, x, target, other, *, s, quant):
    """Per row: (max logit, logit of ``target``, logit of ``other``,
    argmax), the LM head in blocks of rows."""
    T = x.shape[0]
    nb = T // Q_BLOCK
    xn = rms_norm(x, final_norm, s["eps"])

    def block(args):
        xb, tb, ob = args
        lg = _dot("td,dv->tv", xb, lm_head, quant, (1,), (0,))

        def take(t):
            return jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
        return (lg.max(-1), take(tb), take(ob),
                jnp.argmax(lg, -1).astype(jnp.int32))

    outs = jax.lax.map(block, (xn.reshape(nb, Q_BLOCK, -1),
                               target.reshape(nb, Q_BLOCK),
                               other.reshape(nb, Q_BLOCK)))
    return tuple(o.reshape(T) for o in outs)


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, quant):
    return jax.jit(functools.partial(_head, s=dict(frozen), quant=quant))


def _frozen(s):
    return tuple(sorted(s.items()))


def _pad(tokens: np.ndarray) -> np.ndarray:
    """``tokens`` padded to a bucket: a few shapes per cell, each compiled
    once and then found in the compile cache (causal attention leaves the
    rows before the padding as they are)."""
    n = len(tokens)
    size = BUCKET
    while size < n:
        if size == BUCKET:
            size = 2 * BUCKET
        else:
            size = size * 3 // 2 if size & (size - 1) == 0 else size * 4 // 3
    out = np.zeros(size, np.int32)
    out[:n] = tokens
    return out


class Reference:
    """The reference model over one set of weights; a family's subclass
    (``bench/families``) gives its layers as ``_run(tokens, quant)``: the
    final hidden states (T, d) float32 of a padded token sequence.

    ``params``: the weight tree (bf16) as made by ``bench.weights``;
    ``s``: the family's sizes; ``two_t``: None for plain top-k, else the
    family's ``calibrate_2t`` result."""

    def __init__(self, params, s: Dict, two_t: Optional[Dict] = None):
        self.params = params
        self.s = s
        self.two_t = two_t

    def _run(self, tokens, quant):
        raise NotImplementedError

    def rows(self, tokens: np.ndarray, target: np.ndarray,
             other: Optional[np.ndarray] = None, *, quant: bool = False):
        """Forward over ``tokens``; per position p, the row max of the
        next-token logits, the logit of ``target[p]`` and ``other[p]``,
        and the argmax. Arrays cut to ``len(tokens)``."""
        n = len(tokens)
        toks = _pad(tokens)
        tgt = np.zeros_like(toks)
        tgt[:n] = target
        oth = np.zeros_like(toks)
        if other is not None:
            oth[:n] = other
        x = self._run(toks, quant)
        p = self.params
        out = _head_fn(_frozen(self.s), quant)(
            p["final_norm"], p["embed"]["lm_head"], x, jnp.asarray(tgt),
            jnp.asarray(oth))
        return [np.asarray(o)[:n] for o in out]


def served_gaps(ref: Reference, prompt: np.ndarray, out: np.ndarray,
                control: Optional[Reference] = None):
    """Gaps (reference's best logit minus its logit of the served token)
    at every served position of one request; with ``control``, also the
    gaps of the tokens the control would have put first there."""
    tokens = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    n_p = len(prompt)
    target = np.zeros(len(tokens), np.int32)
    target[n_p - 1:] = out
    other = None
    if control is not None:
        other = control.rows(tokens, target, quant=True)[3]
    mx, tl, ol, _ = ref.rows(tokens, target, other)
    gaps = (mx - tl)[n_p - 1:]
    if control is None:
        return gaps, None
    return gaps, (mx - ol)[n_p - 1:]


# ---------------------------------------------------------------------------
# 2T-Drop calibration, as the cell states it
# ---------------------------------------------------------------------------

def _importance(h, w1, w3, idx, method):
    """(E, f) neuron importance over the tokens routed to each expert."""
    def one(e):
        a = jnp.einsum("td,df->tf", h, w1[e].astype(jnp.float32),
                       precision=HIGHEST)
        g = jax.nn.silu(a)
        if method in ("gate_up", "abs_gate_up"):
            g = g * jnp.einsum("td,df->tf", h, w3[e].astype(jnp.float32),
                               precision=HIGHEST)
        if method.startswith("abs"):
            g = jnp.abs(g)
        routed = jnp.any(idx == e, axis=-1)
        return jnp.sum(jnp.where(routed[:, None], g, 0.0), 0)

    return jax.lax.map(one, jnp.arange(w1.shape[0]))


def quantile_index(frac: float, n: int) -> int:
    """Index into n sorted scores of the threshold at drop rate ``frac``,
    the product taken in float32."""
    return int(min(max(np.floor(np.float32(frac) * np.float32(n)), 0), n - 1))
