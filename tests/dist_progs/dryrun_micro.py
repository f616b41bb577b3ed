"""Micro dry-run: the launch machinery (specs + lower + compile + HLO
analysis) on an 8-device mesh with a reduced arch — fast integration check
of repro.launch without the 512-device production mesh."""
import json

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config
from repro.launch import specs
from repro.launch.hlo_analysis import analyze_hlo
from repro.models import model as M
from repro.models.transformer import DistContext
from repro.optim import adamw
from repro.optim.adamw import AdamWState


def main():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    params, axes = M.abstract_params_and_axes(cfg, jnp.float32)
    psh = specs.param_shardings(cfg, params, axes, mesh)
    opt = adamw(1e-4)
    ost = jax.eval_shape(opt.init, params)
    osh = AdamWState(step=jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()), mu=psh, nu=psh)
    batch = specs.abstract_batch(cfg, 8, 64, "train")
    bsh = specs.batch_shardings(cfg, batch, mesh)
    dist = DistContext(mesh=mesh, moe_impl="setp")
    step = M.make_train_step(cfg, opt, dist=dist)
    with jax.set_mesh(mesh):
        comp = jax.jit(step, in_shardings=(psh, osh, bsh)).lower(
            params, ost, batch).compile()
    c = analyze_hlo(comp.as_text())
    print(json.dumps({"status": "ok", "flops": c.flops,
                      "collective_bytes": c.collective_bytes,
                      "by_kind": c.bytes_by_kind}))


if __name__ == "__main__":
    main()
