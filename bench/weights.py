"""Model sizes from a configuration file, and seeded weights.

The benchmark makes the weights itself, on the device, in one jitted call
from the seed, in the type they are served in (bf16). The same call with
the same seed makes the same bits, so the reference, which may take
nothing the program has made, draws them again after the window.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

MATRIX_STD = 0.02     # every projection, expert, router, embedding, head
NORM_JITTER = 0.1     # RMSNorm weights are 1 + NORM_JITTER * normal


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
    return {
        "d": d,
        "layers": cfg["num_hidden_layers"],
        "hq": hq,
        "hkv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // hq,
        "experts": cfg.get("num_experts") or cfg["num_local_experts"],
        "top_k": cfg["num_experts_per_tok"],
        "f": cfg.get("moe_intermediate_size") or cfg["intermediate_size"],
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


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all its bits used."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.lru_cache(maxsize=None)
def _maker(frozen_sizes):
    s = dict(frozen_sizes)
    specs = layout(s)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)

    def make(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            w = 1.0 + NORM_JITTER * z if kind == "norm" else MATRIX_STD * z
            out.append(w.astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


def make(s: Dict, seed: int):
    """bf16 weights for sizes ``s`` drawn from ``seed``, on the device."""
    return _maker(tuple(sorted(s.items())))(key_of(seed))
