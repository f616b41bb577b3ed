"""Distributed serving: the full DualSparse inference system (partition +
reconstruction + 2T-Drop + load-aware thresholds) through the S-ETP
shard_map path on an 8-device mesh, end to end via the serving engine."""
import json

import jax
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM, calibration_activations
from repro.models import model as M
from repro.models.transformer import DistContext
from repro.serving import GenerationConfig, ServingEngine


def main():
    cfg = get_config("olmoe-lite")
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    calib = calibration_activations(jax.random.fold_in(key, 7), 256,
                                    cfg.d_model)
    from repro.core.policy import make_policy
    pol = make_policy("load_aware", cfg.dualsparse)
    tparams, pol = pol.prepare(params, cfg, calib, n_ep_devices=4)
    dist = DistContext(mesh=mesh, moe_impl="setp", policy=pol)
    src = SyntheticLM(cfg.vocab_size)
    prompts = [np.asarray(src.sample_batch(jax.random.fold_in(key, i), 1,
                                           12)["tokens"][0])
               for i in range(2)]
    with jax.set_mesh(mesh):
        eng = ServingEngine(cfg, tparams, batch_size=2, max_prompt_len=12,
                            max_new_tokens=4, dist=dist)
        res = eng.generate(prompts, GenerationConfig(max_new_tokens=4))
    ok = (len(res) == 2 and all(len(r.tokens) == 4 for r in res)
          and all(0 <= t < cfg.vocab_size for r in res for t in r.tokens))
    print(json.dumps({"ok": bool(ok),
                      "tokens": [r.tokens for r in res]}))


if __name__ == "__main__":
    main()
