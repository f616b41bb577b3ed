"""Load-aware histogram on decode steps (run via subprocess, 8 devices).

Regression for the `_setp_body` double-count: on a decode step (S == 1) the
token block is REPLICATED over the expert axis, and the old psum over
``token_axes + (axis,)`` summed n_dev identical per-device histograms —
multiplying every load by n_dev. The body must count each token exactly
once on BOTH paths; we capture the psum'd ``loads`` the policy actually
receives (via a recording ``sub_pair_keep``) and compare decode vs prefill
vs the single-process ground-truth histogram.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_config
from repro.core import dispatch, gating, moe, reconstruct, setp
from repro.core.policy import LoadAwareTwoT
from repro.models.layers import split_params

RECORDED = []


def main():
    cfg = get_config("olmoe-lite")
    key = jax.random.PRNGKey(0)
    params, _ = split_params(moe.make_moe_params(key, cfg))
    params["wg"] = params["wg"] * 20.0          # spread the gating scores
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    n_dev, d = 4, cfg.d_model
    toks = jax.random.normal(jax.random.PRNGKey(1), (8, d)) * 0.5

    pr = reconstruct.partition_and_reconstruct(params, toks, cfg, p=2)
    pr = setp.place_params_strided(pr, n_dev)

    # ground truth: every token counted ONCE, strided sub-expert placement
    r = gating.route(toks, params["wg"], cfg.top_k, cfg.router_norm_topk)
    sub = jnp.arange(2, dtype=r.idx.dtype)
    sub_idx = (r.idx[:, :, None] * 2 + sub).reshape(8, -1)
    expected = np.asarray(dispatch.group_histogram(sub_idx % n_dev, n_dev,
                                                   dtype=jnp.float32))

    orig = LoadAwareTwoT.sub_pair_keep

    def recording(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                  loads=None, thresholds=None):
        def cb(l):
            RECORDED.append(np.asarray(l))
        jax.debug.callback(cb, loads)
        return orig(self, score, is_major, sub_idx, cfg, n_dev=n_dev,
                    loads=loads, thresholds=thresholds)

    LoadAwareTwoT.sub_pair_keep = recording
    la = LoadAwareTwoT(partition_p=2, t_max=cfg.dualsparse.t_max)

    def run(x):
        RECORDED.clear()
        with jax.set_mesh(mesh):
            y = setp.setp_moe_forward(pr, x, cfg, mesh, policy=la,
                                      cap_factor=4.0, local_cap_factor=8.0,
                                      wire_dtype=jnp.float32)
        jax.effects_barrier()
        return np.asarray(y), [l.copy() for l in RECORDED]

    # decode: (B=8, S=1) — seq not divisible by n_dev => tokens REPLICATED
    # over the expert axis (the buggy case)
    y_dec, dec = run(toks.reshape(8, 1, d))
    # prefill: (B=2, S=4) — seq sharded over the expert axis
    y_pre, pre = run(toks.reshape(2, 4, d))

    dec_ok = bool(dec) and all(np.array_equal(l, expected) for l in dec)
    pre_ok = bool(pre) and all(np.array_equal(l, expected) for l in pre)
    print(json.dumps({
        "decode_loads_once": dec_ok,
        "prefill_loads_once": pre_ok,
        "decode_matches_prefill": bool(
            dec and pre and np.array_equal(dec[0], pre[0])),
        "n_records": [len(dec), len(pre)],
        "expected": expected.tolist(),
        "decode_first": dec[0].tolist() if dec else None,
        "finite": bool(np.isfinite(y_dec).all() and np.isfinite(y_pre).all()),
    }))


if __name__ == "__main__":
    main()
