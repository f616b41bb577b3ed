"""The Qwen3-MoE family (``model_type`` ``qwen3_moe``): every layer is
grouped-query attention with rotate-half RoPE, then a top-k MoE block of
SwiGLU experts (softmax router, renormalised where the configuration says
so), with no shared expert and no dense layer.

Its sizes and weight tree, the ``ModelConfig`` the program is given, the
float32 reference's layers and 2T calibration, and the operations and
bytes its served work needs.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, work
from bench import system  # noqa: F401  (puts the program on sys.path)
from bench.reference import Q_BLOCK, _dot, rms_norm, rope
from repro.configs.base import DualSparseConfig, ModelConfig


# ---------------------------------------------------------------------------
# Sizes, weights and the program's configuration
# ---------------------------------------------------------------------------

def sizes(cfg: Dict) -> Dict:
    """The sizes the program and the reference need, from a configuration
    file in the published (Hugging Face) vocabulary."""
    hq = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    if cfg.get("sliding_window") or cfg.get("use_sliding_window"):
        raise ValueError("sliding-window attention is not benchmarked")
    if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
        raise ValueError("every layer must be a MoE layer")
    if cfg.get("attention_bias") or cfg.get("tie_word_embeddings"):
        raise ValueError("attention bias / tied embeddings not benchmarked")
    hkv = cfg["num_key_value_heads"]
    if d % hq or hq % hkv:
        raise ValueError(f"{hq} query heads must divide the width {d} "
                         f"and group over {hkv} KV heads")
    f = cfg.get("moe_intermediate_size") or cfg["intermediate_size"]
    p = cfg["dualsparse"]["partition_p"]
    if f % p:
        raise ValueError(f"expert width {f} does not split into "
                         f"partition_p {p} sub-experts")
    return {
        "d": d,
        "layers": cfg["num_hidden_layers"],
        "hq": hq,
        "hkv": hkv,
        "hd": cfg.get("head_dim") or d // hq,
        "experts": cfg.get("num_experts") or cfg["num_local_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "f": f,
        "vocab": cfg["vocab_size"],
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "renorm": bool(cfg.get("norm_topk_prob", True)),
    }


def layout(s: Dict) -> Dict:
    """Shape of every weight, in the tree the served program takes:
    (shape, "norm" | "matrix")."""
    L, d, hkv, hd = s["layers"], s["d"], s["hkv"], s["hd"]
    g = s["hq"] // hkv
    E, f, V = s["experts"], s["f"], s["vocab"]
    return {
        "embed": {"embedding": ((V, d), "matrix"),
                  "lm_head": ((d, V), "matrix")},
        "final_norm": ((d,), "norm"),
        "blocks": {
            "ln1": ((L, d), "norm"),
            "ln2": ((L, d), "norm"),
            "attn": {"wq": ((L, d, hkv, g, hd), "matrix"),
                     "wk": ((L, d, hkv, hd), "matrix"),
                     "wv": ((L, d, hkv, hd), "matrix"),
                     "wo": ((L, hkv, g, hd, d), "matrix")},
            "moe": {"wg": ((L, d, E), "matrix"),
                    "w1": ((L, E, d, f), "matrix"),
                    "w3": ((L, E, d, f), "matrix"),
                    "w2": ((L, E, f, d), "matrix")},
        },
    }


def model_config(name: str, cfg: Dict, s: Dict) -> ModelConfig:
    ds = cfg["dualsparse"]
    return ModelConfig(
        arch_id=name, family="moe", source=cfg["source"],
        n_layers=s["layers"], d_model=s["d"], n_heads=s["hq"],
        n_kv_heads=s["hkv"], head_dim=s["hd"], d_ff=s["f"],
        vocab_size=s["vocab"], attn_kind="gqa", rope_theta=s["theta"],
        n_experts=s["experts"], top_k=s["top_k"], d_expert=s["f"],
        router_norm_topk=s["renorm"], norm_eps=s["eps"],
        tie_embeddings=False,
        dualsparse=DualSparseConfig(
            enabled=True, partition_p=ds["partition_p"],
            t_drop=ds["t_drop"], t_major=ds["t_major"],
            t_minor=ds["t_minor"], importance=ds["importance"],
            t_max=ds["t_max"]))


# ---------------------------------------------------------------------------
# The float32 reference's layers
# ---------------------------------------------------------------------------

def _attention(q, k, v, quant):
    """Causal attention; q (T,Hkv,G,D), k/v (T,Hkv,D), T % Q_BLOCK == 0."""
    T, hkv, g, hd = q.shape
    nb = T // Q_BLOCK
    kpos = jnp.arange(T)

    def block(args):
        qb, b = args
        s = _dot("qhgd,thd->qhgt", qb, k, quant, (3,), (2,)) / np.sqrt(hd)
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return _dot("qhgt,thd->qhgd", p, v, quant, (3,), (0,))

    out = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, hkv, g, hd),
                              jnp.arange(nb)))
    return out.reshape(T, hkv, g, hd)


def _layer(blocks, l, x, thr, major, *, s, two_t, quant, capture=False):
    """One decoder layer over a whole (padded) sequence x (T, d)."""
    at, moe = blocks["attn"], blocks["moe"]
    T = x.shape[0]
    pos = jnp.arange(T)
    h = rms_norm(x, blocks["ln1"][l], s["eps"])
    q = rope(_dot("td,dhgk->thgk", h, at["wq"][l], quant, (1,), (0,)),
             pos, s["theta"])
    k = rope(_dot("td,dhk->thk", h, at["wk"][l], quant, (1,), (0,)),
             pos, s["theta"])
    v = _dot("td,dhk->thk", h, at["wv"][l], quant, (1,), (0,))
    o = _attention(q, k, v, quant)
    x = x + _dot("thgk,hgkd->td", o, at["wo"][l], quant, (1, 2, 3),
                 (0, 1, 2))
    h = rms_norm(x, blocks["ln2"][l], s["eps"])
    y = reference.moe_layer(h, moe, l, thr, major, s=s, two_t=two_t,
                            quant=quant)
    if capture:
        return x + y, h
    return x + y


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, two_t, quant, capture):
    s = dict(frozen)
    return jax.jit(functools.partial(_layer, s=s, two_t=two_t, quant=quant,
                                     capture=capture))


class Reference(reference.Reference):
    """The reference model over one set of weights.

    ``params``: the weight tree (bf16) as made by ``bench.weights``;
    ``s``: sizes from :func:`sizes`; ``two_t``: None for plain top-k, else
    a dict with per-layer ``thresholds`` (L, 2) and ``major`` (L, E, f)
    bool from :func:`calibrate_2t`."""

    def _run(self, tokens, quant, capture=False):
        s = self.s
        blocks = self.params["blocks"]
        x = self.params["embed"]["embedding"][jnp.asarray(tokens)].astype(
            jnp.float32)
        L, E, f = s["layers"], s["experts"], s["f"]
        if self.two_t is not None:
            thr, major = self.two_t["thresholds"], self.two_t["major"]
        else:
            thr = jnp.zeros((L, 2), jnp.float32)
            major = jnp.zeros((L, E, f), bool)
        fn = _layer_fn(reference._frozen(s),
                       self.two_t is not None and not capture, quant, capture)
        hs = []
        for layer in range(L):
            out = fn(blocks, jnp.int32(layer), x, thr, major)
            if capture:
                x, h = out
                hs.append(h)
            else:
                x = out
        return (x, hs) if capture else x

    def calibration(self, prompts: List[np.ndarray]) -> np.ndarray:
        """The stack the program's policy calibrates on: per MoE layer (here
        every layer), the hidden states entering its router, with every
        pair computed whole, over all ``prompts`` one after the other;
        (layers, tokens, d) float32."""
        per_prompt = []
        for tokens in prompts:
            _, hs = self._run(reference._pad(tokens), quant=False,
                              capture=True)
            per_prompt.append([np.asarray(h[:len(tokens)]) for h in hs])
        return np.stack([np.concatenate([hs[layer] for hs in per_prompt])
                         for layer in range(len(per_prompt[0]))])


def calibrate_2t(params, s: Dict, calib, *, p: int, importance: str,
                 drop_target: float, delta: float) -> Dict:
    """Per-layer thresholds (L, 2) and major-neuron masks (L, E, f) from
    calibration activations ``calib`` ((T, d) per layer)."""
    moe = params["blocks"]["moe"]
    thr, major = [], []
    f = s["f"]
    for layer, h in enumerate(calib):
        h = jnp.asarray(h, jnp.float32)
        idx, _, norm = reference.route(h, moe["wg"][layer], s)
        flat = np.sort(np.asarray(norm).reshape(-1))
        n = flat.size
        tm = flat[reference.quantile_index(max(drop_target - delta, 0.0), n)]
        tn = flat[reference.quantile_index(min(drop_target + delta, 1.0), n)]
        thr.append([tm, tn])
        imp = reference._importance(h, moe["w1"][layer], moe["w3"][layer],
                                    idx, importance)
        order = np.argsort(-np.asarray(imp), axis=-1, kind="stable")
        mask = np.zeros((s["experts"], f), bool)
        np.put_along_axis(mask, order[:, :f // p], True, axis=-1)
        major.append(mask)
    return {"thresholds": jnp.asarray(np.array(thr, np.float32)),
            "major": jnp.asarray(np.stack(major))}


# ---------------------------------------------------------------------------
# Operations and bytes of the served work (see bench/work.py)
# ---------------------------------------------------------------------------

def proj_flops(s: Dict) -> float:
    """Q/K/V/O projections, one token, one layer."""
    return 2.0 * s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])


def core_flops(s: Dict, keys: float) -> float:
    """QK^T and PV of one query over ``keys`` keys, one layer."""
    return 4.0 * s["hq"] * s["hd"] * keys


def router_flops(s: Dict) -> float:
    return 2.0 * s["d"] * s["experts"]


def moe_flops(s: Dict, kept_share: float) -> float:
    """Expert SwiGLU products of one token, one layer, when ``kept_share``
    of its sub-pairs are kept (1 without dropping)."""
    return s["top_k"] * kept_share * 6.0 * s["d"] * s["f"]


def head_flops(s: Dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


def sub_expert_bytes(s: Dict, p: int) -> float:
    """bf16 bytes of one (sub-)expert's three matrices."""
    return 3.0 * s["d"] * (s["f"] // p) * 2


def model_flops(s: Dict, steps: Sequence[Dict], kept_share: float) -> float:
    """Needed model operations of ``steps``."""
    L = s["layers"]
    per_token = proj_flops(s) + router_flops(s) + moe_flops(s, kept_share)
    total = 0.0
    for rec in steps:
        keys = float(sum(rec["decode_keys"]))
        heads = len(rec["decode_keys"])
        if rec["chunk"]:
            start, valid, final = rec["chunk"]
            keys += work.chunk_keys(start, valid)
            heads += int(final)
        total += L * (work.step_tokens(rec) * per_token
                      + core_flops(s, keys))
        total += heads * head_flops(s)
    return total


def moe_needed(s: Dict, p: int, steps: Sequence[Dict],
               kept_share: float) -> Dict[str, float]:
    """Operations and bytes the MoE layers of ``steps`` need: the kept
    sub-pairs' products; each step's live (sub-)experts' weights (read
    once for the step's calls together, a lower bound), and every token's
    row in and out."""
    tokens = sum(work.step_tokens(r) for r in steps)
    flops = tokens * s["layers"] * moe_flops(s, kept_share)
    weights = sum(float(np.sum(r["live"])) for r in steps) \
        * sub_expert_bytes(s, p)
    rows = tokens * s["layers"] * s["d"] * 2 * 2
    return {"flops": flops, "bytes": weights + rows}
