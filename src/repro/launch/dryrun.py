# The dry-run (and ONLY the dry-run) builds the production mesh out of 512
# placeholder host CPU devices. These lines MUST run before any other import
# (jax locks the platform and device count on first init).
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.configs.base import InputShape, ModelConfig
from repro.launch import mesh as mesh_mod
from repro.launch import specs
from repro.launch.hlo_analysis import roofline_terms
from repro.models import model as M
from repro.models.transformer import DistContext
from repro.optim import adamw

# (arch, shape) pairs that do not lower, with the DESIGN.md §5 reason.
SKIPS = {
    ("whisper-large-v3", "long_500k"):
        "enc-dec with bounded decoder context; 500k decode is architecturally"
        " meaningless (DESIGN.md §5)",
}


def build_dist(cfg: ModelConfig, kind: str, mesh) -> DistContext:
    """MoE archs: S-ETP EP always; the DualSparse inference system as a
    SparsityPolicy (load_aware when the config asks for it, else 2t) on the
    serving paths."""
    from repro.core.policy import make_policy
    serving = kind in ("prefill", "decode")
    pol = None
    if cfg.is_moe and cfg.dualsparse.enabled and serving:
        name = "load_aware" if cfg.dualsparse.load_aware else "2t"
        pol = make_policy(name, cfg.dualsparse)
    return DistContext(mesh=mesh, moe_impl="setp", policy=pol,
                       remat=(kind == "train"), remat_policy="dots")


def abstract_state(cfg: ModelConfig, shape: InputShape, mesh):
    """(abstract args, in_shardings, step_fn) for the given shape kind."""
    kind = shape.kind
    window = specs.decode_window(cfg, shape)
    dist = build_dist(cfg, kind, mesh)
    n_ep = mesh.shape["model"]

    if kind == "train":
        params, axes = M.abstract_params_and_axes(cfg, jnp.float32)
    else:
        params, axes = M.abstract_params_and_axes(cfg, jnp.bfloat16)
        if dist.policy is not None and dist.policy.partition_p > 1:
            def xf(p):
                calib = jnp.zeros((256, cfg.d_model), jnp.float32)
                return dist.policy.prepare(p, cfg, calib,
                                           n_ep_devices=n_ep)[0]
            new_params = jax.eval_shape(xf, params)
            axes = _retree_axes(axes, new_params)
            params = new_params
        elif cfg.is_moe:
            # plain S-ETP still needs strided placement (id-preserving shapes)
            pass
    p_shard = specs.param_shardings(cfg, params, axes, mesh)

    if kind == "train":
        opt = adamw(1e-4)
        opt_state = jax.eval_shape(opt.init, params)
        # AdamWState is a NamedTuple: params shardings map onto mu/nu
        from repro.optim.adamw import AdamWState
        o_shard = AdamWState(
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            mu=p_shard, nu=p_shard)
        batch = specs.abstract_batch(cfg, shape.global_batch, shape.seq_len,
                                     "train")
        b_shard = specs.batch_shardings(cfg, batch, mesh)
        step = M.make_train_step(cfg, opt, window=window, dist=dist)
        return (params, opt_state, batch), (p_shard, o_shard, b_shard), step

    if kind == "prefill":
        batch = specs.abstract_batch(cfg, shape.global_batch, shape.seq_len,
                                     "prefill")
        b_shard = specs.batch_shardings(cfg, batch, mesh)
        step = M.make_prefill_step(cfg, cache_len=shape.seq_len,
                                   window=window, dist=dist)
        return (params, batch), (p_shard, b_shard), step

    # decode: ONE token against a seq_len cache
    ctx = min(window, shape.seq_len) if window else shape.seq_len
    cache = M.abstract_cache(cfg, shape.global_batch, shape.seq_len,
                             window=window)
    c_shard = specs.cache_shardings(cfg, cache, mesh)
    token = specs.sds((shape.global_batch, 1), jnp.int32)
    t_shard = specs.batch_shardings(cfg, {"t": token}, mesh)["t"]
    step = M.make_serve_step(cfg, window=window, dist=dist)
    return (params, token, cache), (p_shard, t_shard, c_shard), step


def _retree_axes(axes, new_params):
    """Axes tree for transformed params: same structure, reuse where leaf
    paths match, default replicated-expert axes for the moe leaves."""
    flat_new = jax.tree_util.tree_flatten_with_path(new_params)[0]
    flat_old = dict(jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))[0])
    out = []
    for path, leaf in flat_new:
        if path in flat_old:
            out.append(flat_old[path])
        else:
            out.append((None,) * len(leaf.shape))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(new_params), out)


def _per_device_param_bytes(params_abs, shardings) -> int:
    """Per-device bytes of the (sharded) param arguments."""
    total = 0
    for leaf, sh in zip(jax.tree.leaves(params_abs),
                        jax.tree.leaves(shardings,
                                        is_leaf=lambda x: hasattr(x, "spec"))):
        n = leaf.size * jnp.dtype(leaf.dtype).itemsize
        shard = 1
        for entry in sh.spec:
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                if ax is not None:
                    shard *= sh.mesh.shape[ax]
        total += n // max(shard, 1)
    return total


def run_one(arch: str, shape_name: str, multi_pod: bool,
            donate: bool = True) -> Dict[str, Any]:
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
    }
    if (arch, shape_name) in SKIPS:
        rec.update(status="skipped", reason=SKIPS[(arch, shape_name)])
        return rec
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    n_chips = 512 if multi_pod else 256
    try:
        t0 = time.time()
        args, shardings, step = abstract_state(cfg, shape, mesh)
        jitted = jax.jit(step, in_shardings=shardings,
                         donate_argnums=tuple(range(len(args))) if donate
                         and shape.kind != "prefill" else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        # XLA's HloCostAnalysis counts while bodies once, so flops/bytes come
        # from our own trip-count-scaled HLO analysis (hlo_analysis.py).
        from repro.launch.hlo_analysis import analyze_hlo
        costs = analyze_hlo(compiled.as_text())
        rec["flops"] = costs.flops                      # per device
        rec["hlo_bytes_proxy"] = costs.hbm_bytes        # upper-bound proxy
        ca = compiled.cost_analysis() or {}
        rec["xla_flops_1iter"] = float(ca.get("flops", -1.0))
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            rec["memory"] = {"error": str(e)}
        rec["collectives"] = {
            "bytes_by_kind": costs.bytes_by_kind,
            "count_by_kind": costs.count_by_kind,
            "total_bytes": costs.collective_bytes,
        }
        # memory term: every argument read once + outputs written + temps
        # touched twice (activation write+read). The CPU backend's
        # FloatNormalization pass materializes f32 copies of every bf16
        # weight (a compile-target artifact that does not exist on TPU), so
        # for bf16-param steps we subtract that known 2x-param temp before
        # weighting temps. Params' per-device bytes follow from the
        # in_shardings.
        mem = rec["memory"]
        traffic = 0.0
        if mem.get("argument_bytes") is not None:
            temp = mem.get("temp_bytes") or 0
            if shape.kind != "train":
                pdev = _per_device_param_bytes(args[0], shardings[0])
                rec["param_bytes_per_device"] = pdev
                temp = max(temp - 2 * pdev, 0)
            rec["temp_bytes_adjusted"] = temp
            traffic = (mem["argument_bytes"] + (mem.get("output_bytes") or 0)
                       + 2 * temp)
        rec["hbm_traffic_bytes"] = traffic
        rec["roofline"] = roofline_terms(
            costs.flops, traffic, costs.collective_bytes, 1,
            peak_flops=mesh_mod.PEAK_FLOPS_BF16, hbm_bw=mesh_mod.HBM_BW,
            ici_bw=mesh_mod.ICI_BW)
        rec["n_chips"] = n_chips
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    return rec


def main():
    ap = argparse.ArgumentParser(description="Multi-pod dry-run")
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS + ["all"], default=None)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"],
                    default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape x mesh) via subprocesses")
    ap.add_argument("--out", default=None)
    ap.add_argument("--jobs", type=int, default=2)
    args = ap.parse_args()

    if args.all or args.arch == "all" or args.shape == "all":
        archs = ASSIGNED_ARCHS if args.arch in (None, "all") else [args.arch]
        shapes = list(INPUT_SHAPES) if args.shape in (None, "all") \
            else [args.shape]
        meshes = [False, True] if (args.both_meshes or args.all) \
            else [args.multi_pod]
        combos = [(a, s, m) for a in archs for s in shapes for m in meshes]
        _run_many(combos, args.out, args.jobs)
        return

    rec = run_one(args.arch, args.shape, args.multi_pod)
    line = json.dumps(rec)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    summary = {k: rec.get(k) for k in
               ("arch", "shape", "mesh", "status", "compile_s", "flops",
                "hlo_bytes", "error")}
    print(json.dumps(summary, indent=1))
    if rec["status"] == "ok":
        print("collectives:", json.dumps(rec["collectives"]))
        print("memory:", json.dumps(rec["memory"]))
        print("roofline(s):", json.dumps(rec["roofline"]))
    elif rec["status"] == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
        sys.exit(1)


def _run_many(combos, out: Optional[str], jobs: int):
    """Subprocess per combo (isolates compile memory), bounded parallelism."""
    procs: list = []
    pending = list(combos)
    results = []

    def launch(combo):
        a, s, m = combo
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", a, "--shape", s] + (["--multi-pod"] if m else [])
        if out:
            cmd += ["--out", out]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return combo, subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                       stderr=subprocess.DEVNULL, env=env)

    while pending or procs:
        while pending and len(procs) < jobs:
            procs.append(launch(pending.pop(0)))
        done = [p for p in procs if p[1].poll() is not None]
        for combo, proc in done:
            procs.remove((combo, proc))
            ok = proc.returncode == 0
            print(f"[{'OK' if ok else 'FAIL'}] {combo}", flush=True)
            results.append((combo, ok))
        if not done:
            time.sleep(2)
    n_ok = sum(1 for _, ok in results if ok)
    print(f"{n_ok}/{len(results)} combos lowered+compiled")


if __name__ == "__main__":
    main()
