"""Paper Fig 9: S-ETP vs ETP communication. We count exact collective bytes
and ops from the compiled HLO (the TPU analogue of the paper's NCCL
bandwidth test) for the paper's real-world configs (E2T4 / E4T2 on 8
devices) and simulated NVL72 (EP9xTP8) / CloudMatrix384 (EP48xTP8).

Runs in CPU subprocesses because each mesh needs its own
--xla_force_host_platform_device_count; a failed child fails the run."""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import Row

_PROG = r"""
import dataclasses, json, sys
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs import get_config
from repro.core import moe, setp
from repro.launch.hlo_analysis import analyze_hlo
from repro.models.layers import split_params

ep, tp, tokens = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
# expert count must tile the EP axis (paper's simulated meshes put whole
# experts on EP ranks): E = ep * ceil(8/ep)
E = ep * max(1, (8 + ep - 1) // ep)
cfg = dataclasses.replace(get_config("mixtral-8x7b-lite"), n_experts=E)
key = jax.random.PRNGKey(0)
params, _ = split_params(moe.make_moe_params(key, cfg))
x = jax.ShapeDtypeStruct((ep, tokens, cfg.d_model), jnp.float32)

# ETP: EP x TP mesh
mesh = jax.make_mesh((ep, tp), ("ep", "tp"),
                     axis_types=(AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    comp = jax.jit(lambda p, xx: setp.etp_moe_forward(
        p, xx, cfg, mesh, cap_factor=1.5)).lower(params, x).compile()
etp = analyze_hlo(comp.as_text())

# S-ETP: partial transform P=tp, pure EP over ep*tp devices, expressed as
# a keep-everything 2T policy with partition factor P=tp
p_factor = tp
pp = setp.place_params_strided(
    __import__("repro.core.partition", fromlist=["partial_transform"])
    .partial_transform(params, p_factor), ep * tp)
mesh2 = jax.make_mesh((1, ep * tp), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
from repro.core.policy import TwoTDrop
pol = TwoTDrop(partition_p=p_factor, t_major=-1.0, t_minor=-1.0)
x2 = jax.ShapeDtypeStruct((1, ep * tokens, cfg.d_model), jnp.float32)
with jax.set_mesh(mesh2):
    comp2 = jax.jit(lambda p, xx: setp.setp_moe_forward(
        p, xx, cfg, mesh2, policy=pol, cap_factor=1.5,
        cap_multiple=1)).lower(pp, x2).compile()
s_etp = analyze_hlo(comp2.as_text())

print(json.dumps({"etp": etp.bytes_by_kind, "etp_total": etp.collective_bytes,
                  "setp": s_etp.bytes_by_kind,
                  "setp_total": s_etp.collective_bytes}))
"""

CONFIGS = [
    ("E2T4", 2, 4, 512),
    ("E4T2", 4, 2, 512),
    ("NVL72", 9, 8, 512),
    ("CM384", 48, 8, 512),
]


def run() -> list[Row]:
    rows: list[Row] = []
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name, ep, tp, tokens in CONFIGS:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{ep * tp}")
        env["PYTHONPATH"] = os.path.join(root, "src")
        # placeholder host devices: the child never touches an accelerator
        env["JAX_PLATFORMS"] = "cpu"
        p = subprocess.run([sys.executable, "-c", _PROG, str(ep), str(tp),
                            str(tokens)], capture_output=True, text=True,
                           env=env, timeout=900)
        if p.returncode != 0:
            raise SystemExit(f"fig9/{name}: child exited {p.returncode}: "
                             f"{p.stderr[-2000:]}")
        res = json.loads(p.stdout.strip().splitlines()[-1])
        ratio = res["etp_total"] / max(res["setp_total"], 1)
        rows.append((
            f"fig9/{name}(EP{ep}xTP{tp})", 0.0,
            f"etp_bytes={res['etp_total']:.3g} setp_bytes="
            f"{res['setp_total']:.3g} reduction={ratio:.2f}x "
            f"setp_kinds={sorted(res['setp'])} etp_kinds={sorted(res['etp'])}"
        ))
    return rows
