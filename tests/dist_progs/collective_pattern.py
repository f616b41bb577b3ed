"""Fig 5 / Fig 9 structural check: S-ETP lowers to AlltoAll only; ETP lowers
to AlltoAll + AllGather + ReduceScatter, and moves more bytes."""
import json

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config
from repro.core import moe, setp
from repro.launch.hlo_analysis import analyze_hlo
from repro.models.layers import split_params


def main():
    cfg = get_config("olmoe-lite")
    key = jax.random.PRNGKey(0)
    params, _ = split_params(moe.make_moe_params(key, cfg))
    B, S, d = 8, 32, cfg.d_model
    x = jax.ShapeDtypeStruct((B, S, d), jnp.float32)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    pl = setp.place_params_strided(params, 4)
    with jax.set_mesh(mesh):
        comp = jax.jit(lambda p, xx: setp.setp_moe_forward(
            p, xx, cfg, mesh, cap_factor=2.0)).lower(pl, x).compile()
    c1 = analyze_hlo(comp.as_text())

    mesh2 = jax.make_mesh((4, 2), ("ep", "tp"),
                          axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh2):
        comp2 = jax.jit(lambda p, xx: setp.etp_moe_forward(
            p, xx, cfg, mesh2, cap_factor=2.0)).lower(params, x).compile()
    c2 = analyze_hlo(comp2.as_text())

    print(json.dumps({
        "setp": c1.bytes_by_kind, "etp": c2.bytes_by_kind,
        "setp_bytes": c1.collective_bytes, "etp_bytes": c2.collective_bytes,
    }))


if __name__ == "__main__":
    main()
