"""Actually EXECUTE a distributed train step (8 host devices): S-ETP MoE,
sharded params, two steps, loss finite and moving."""
import json

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config
from repro.data import pipeline
from repro.launch import specs
from repro.models import model as M
from repro.models.transformer import DistContext
from repro.optim import adamw


def main():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    params, axes = M.init_params_and_axes(key, cfg)
    psh = specs.param_shardings(cfg, params, axes, mesh)
    params = jax.device_put(params, psh)
    opt = adamw(3e-3)
    ost = opt.init(params)
    dist = DistContext(mesh=mesh, moe_impl="setp")
    step = jax.jit(M.make_train_step(cfg, opt, dist=dist))
    loader = pipeline.make_loader(cfg, 8, 32)
    losses = []
    with jax.set_mesh(mesh):
        for i in range(6):
            params, ost, loss = step(params, ost, loader.get_batch(i))
            losses.append(float(loss))
    print(json.dumps({"loss_finite": all(jnp.isfinite(jnp.array(losses))),
                      "loss0": losses[0], "loss1": losses[-1]}))


if __name__ == "__main__":
    main()
