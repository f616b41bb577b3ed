"""S-ETP / ETP exactness on an 8-device host mesh (run via subprocess)."""
import json

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.configs import get_config
from repro.core import moe, setp, reconstruct
from repro.models.layers import split_params


def main():
    cfg = get_config("olmoe-lite")
    key = jax.random.PRNGKey(0)
    params, _ = split_params(moe.make_moe_params(key, cfg))
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    B, S, d = 4, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.5
    y_ref = moe.moe_forward_ref(params, x.reshape(-1, d), cfg).reshape(B, S, d)

    pl = setp.place_params_strided(params, 4)
    with jax.set_mesh(mesh):
        y = setp.setp_moe_forward(pl, x, cfg, mesh, cap_factor=4.0,
                                  local_cap_factor=8.0,
                                  wire_dtype=jnp.float32)
    plain_err = float(jnp.abs(y - y_ref).max())

    from repro.core.policy import LoadAwareTwoT, NoDrop, TwoTDrop
    pr = reconstruct.partition_and_reconstruct(params, x.reshape(-1, d), cfg,
                                               p=2)
    pr = setp.place_params_strided(pr, 4)
    keep_all = TwoTDrop(partition_p=2, t_major=-1.0, t_minor=-1.0)
    with jax.set_mesh(mesh):
        y2 = setp.setp_moe_forward(pr, x, cfg, mesh, policy=keep_all,
                                   cap_factor=4.0, local_cap_factor=8.0,
                                   wire_dtype=jnp.float32)
    ds_err = float(jnp.abs(y2 - y_ref).max())

    la = LoadAwareTwoT(partition_p=2, t_max=cfg.dualsparse.t_max)
    with jax.set_mesh(mesh):
        y3 = setp.setp_moe_forward(pr, x, cfg, mesh, policy=la,
                                   cap_factor=4.0, local_cap_factor=8.0,
                                   wire_dtype=jnp.float32)
    la_finite = bool(jnp.isfinite(y3).all())

    # exact capacity (the engines' exact_moe) on a skewed router: the
    # default capacity factors overflow, the worst-case seating does not
    skew = dict(params, wg=params["wg"] * 20.0)
    y_skew_ref = moe.moe_forward_ref(skew, x.reshape(-1, d),
                                     cfg).reshape(B, S, d)
    pl_skew = setp.place_params_strided(skew, 4)
    with jax.set_mesh(mesh):
        _, of_default = setp.setp_moe_forward(
            pl_skew, x, cfg, mesh, return_overflow=True,
            wire_dtype=jnp.float32)
        y5, of_exact = setp.setp_moe_forward(
            pl_skew, x, cfg, mesh, policy=NoDrop(exact_capacity=True),
            return_overflow=True, wire_dtype=jnp.float32)
    exact_err = float(jnp.abs(y5 - y_skew_ref).max())

    mesh2 = jax.make_mesh((4, 2), ("ep", "tp"),
                          axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh2):
        y4 = setp.etp_moe_forward(params, x, cfg, mesh2, cap_factor=4.0,
                                  local_cap_factor=8.0)
    etp_err = float(jnp.abs(y4 - y_ref).max())

    print(json.dumps({"plain_err": plain_err,
                      "dualsparse_keepall_err": ds_err,
                      "load_aware_finite": la_finite,
                      "etp_err": etp_err,
                      "default_overflow": int(of_default),
                      "exact_overflow": int(of_exact),
                      "exact_err": exact_err}))


if __name__ == "__main__":
    main()
