#!/usr/bin/env python3
"""Smoke test of the served path on a TPU, at published model widths.

    python chip_smoke.py              # one chip: paged engine, two policies
    python chip_smoke.py --chips 4    # four chips: S-ETP expert parallelism

One chip (the default). Qwen3-30B-A3B at its published widths, cut from 48
to 4 layers, with seeded random bf16 weights, is served through
``PagedEngine`` (``submit``/``step``/``drain``) twice:

  A. policy ``none`` (plain top-8 MoE);
  B. policy ``per_layer`` at ``drop_target=0.25`` (partition P=2 +
     reconstruction + 2T-Drop; the mode-grouped kernel skips minor halves).

Each phase serves 8 seeded requests (prompts of 64-256 tokens, 32 new
tokens each), checks that the chunk and decode steps traced once, that no
pair overflowed, and that the compiled decode step runs the Pallas kernel.
One full-width MoE layer is then compared on the chip with the float32
dense reference (``moe_forward_ref``) on the same routed pairs.

Four chips (``--chips 4``). The same 4-layer model is created directly
sharded on a (1, 4) ("data", "model") mesh, experts partitioned and placed
strided over the chips, and one 4 x 256-token prefill runs through S-ETP
(``DistContext(moe_impl="setp")``) under load-aware 2T-Drop. It is compared
with the same prefill on one chip through the dispatch path under the same
thresholds.

Timings printed here are smoke timings of this run, not benchmark results.
Any failed check exits non-zero. The last line of standard output is one
JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

# Tolerances. The kernel computes in bf16 with f32 accumulation (its SwiGLU
# hidden state is rounded to bf16 before the down projection, its output
# to bf16 at the end); the reference runs the same bf16 values in f32.
MOE_LAYER_REL_TOL = 1e-2        # relative L2 error, one MoE layer
# S-ETP vs dispatch: same weights, thresholds and exact capacities, but
# every layer rounds differently (bf16 sub-expert outputs on the wire,
# sharded attention), so routing scores near a 2T threshold can flip a
# half between the paths.
DIST_LOGITS_REL_TOL = 5e-2      # relative L2 error of the prefill logits
DIST_ARGMAX_AGREE_MIN = 0.95    # share of positions whose argmax agrees

N_LAYERS = 4
N_REQUESTS = 8
PROMPT_LENS = (64, 256)         # drawn uniformly, inclusive
NEW_TOKENS = 32
ENGINE = dict(n_slots=4, page_size=16, chunk_size=128, max_prompt_len=256,
              max_new_tokens=NEW_TOKENS)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: paged serving, two policies; 4: S-ETP only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform!r}); "
              "nothing to run", file=sys.stderr)
        sys.exit(2)
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}")

    from repro.configs import get_config
    cfg = get_config("qwen3-moe-30b-a3b")
    print(f"config {cfg.arch_id}: published widths d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"experts={cfg.n_experts} top_k={cfg.top_k} "
          f"d_expert={cfg.d_expert} vocab={cfg.vocab_size} "
          f"rope_theta={cfg.rope_theta:g}; depth cut {cfg.n_layers} -> "
          f"{N_LAYERS} layers; weights bf16 from seed {args.seed}")
    cfg = dataclasses.replace(cfg, n_layers=N_LAYERS)

    dev = devices[0]
    if args.chips == 4:
        four_chips(cfg, devices[:4], args.seed)
    else:
        one_chip(cfg, dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


# ---------------------------------------------------------------------------
# one chip: paged serving under two policies
# ---------------------------------------------------------------------------

def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def _n_params(params) -> int:
    import jax
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))


def one_chip(cfg, dev, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.policy import make_policy
    from repro.data.pipeline import calibration_activations
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.models.transformer import DistContext

    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        M.init_params(key, cfg, dtype=jnp.bfloat16))
    n = _n_params(params)
    print(f"params: {n} ({n * 2 / 1e9:.2f} GB bf16) built on "
          f"{dev.device_kind} in {time.perf_counter() - t0:.1f}s; "
          f"memory {_memory(dev)}")

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab_size, int(n_tok)).astype(np.int32)
               for n_tok in lens]
    print(f"requests: {N_REQUESTS} prompts of lengths {lens.tolist()}, "
          f"{NEW_TOKENS} new tokens each")

    # phase A: plain top-k MoE
    serve_phase("A", cfg, params, None, prompts, dev)
    check_moe_layer("A", cfg, params, make_policy("none"), seed, dev)
    # an engine's jitted steps close over the engine (trace counters), a
    # reference cycle: collect it so phase A's expert stack can be freed
    gc.collect()

    # phase B: per-layer calibrated 2T-Drop at the paper's operating point.
    # Only the MoE subtree is prepared (under jit, so XLA holds the old and
    # new expert stacks and nothing more); the rest is shared with phase A.
    policy = make_policy("per_layer", cfg.dualsparse, drop_target=0.25)
    calib = calibration_activations(jax.random.fold_in(key, 7), 512,
                                    cfg.d_model).astype(jnp.bfloat16)
    t0 = time.perf_counter()
    moe_b, policy = jax.jit(lambda m, c: policy.prepare(
        {"blocks": {"moe": m}}, cfg, c))(params["blocks"]["moe"], calib)
    params = {**params, "blocks": {**params["blocks"],
                                   "moe": moe_b["blocks"]["moe"]}}
    jax.block_until_ready(params)
    print(f"phase B prepare (partition P={policy.partition_p} + "
          f"reconstruction + per-layer thresholds): "
          f"{time.perf_counter() - t0:.1f}s; memory {_memory(dev)}")
    dist = DistContext(mesh=make_host_mesh(1), moe_impl="dispatch",
                       policy=policy)
    serve_phase("B", cfg, params, dist, prompts, dev)
    check_moe_layer("B", cfg, params, policy, seed, dev)

    mem = _memory(dev)
    peak, limit = mem["peak_bytes_in_use"], mem["bytes_limit"]
    print(f"peak_bytes_in_use: {peak} of bytes_limit {limit} "
          f"on {dev.device_kind}")
    check(peak is not None and peak < 16e9,
          f"peak bytes {peak} not under 16 GB")


def _subpair_counts(snap) -> dict:
    out = {}
    for outcome in ("kept_full", "kept_major", "dropped", "overflow"):
        series = f'repro_moe_subpairs_total{{outcome="{outcome}"}}'
        out[outcome] = int(snap.counters.get(series, 0))
    return out


def serve_phase(name, cfg, params, dist, prompts, dev) -> None:
    import jax
    from repro.serving import GenerationConfig, PagedEngine

    policy = dist.policy.name if dist is not None else "none"
    eng = PagedEngine(cfg, params, dist=dist, **ENGINE)
    gen = GenerationConfig(max_new_tokens=NEW_TOKENS)
    uids = [eng.submit(p, gen) for p in prompts]
    results = eng.drain()
    check([r.uid for r in results] == uids, f"phase {name}: results "
          "out of submission order")
    n_tok = sum(len(r.tokens) for r in results)
    check(all(len(r.tokens) == NEW_TOKENS for r in results),
          f"phase {name}: a request stopped short of {NEW_TOKENS} tokens")
    check(all(0 <= t < cfg.vocab_size for r in results for t in r.tokens),
          f"phase {name}: token id outside the vocabulary")
    t = eng.timing
    print(f"phase {name} policy={policy}: served {len(results)} requests, "
          f"{n_tok} tokens ({eng.chunk_steps} prefill chunks, "
          f"{eng.decode_steps} decode steps)")
    print(f"phase {name} smoke timings on {dev.device_kind} (not a "
          f"benchmark): compile_s={t['compile_s']:.2f} over "
          f"{int(t['compile_steps'])} traced steps, steady step "
          f"{t['steady_step_s'] * 1e3:.2f} ms over "
          f"{int(t['steady_steps'])} steps")
    traces = (eng.chunk_traces, eng.decode_traces)
    counts = _subpair_counts(eng.metrics())
    print(f"phase {name}: chunk_traces={traces[0]} decode_traces="
          f"{traces[1]} overflow_pairs={eng.overflow_pairs} "
          f"kept_full={counts['kept_full']} kept_major="
          f"{counts['kept_major']} dropped={counts['dropped']}")
    n_kernels = eng.decode_hlo().count("tpu_custom_call")
    print(f"phase {name}: tpu_custom_call in compiled decode step: "
          f"{n_kernels}")
    check(traces == (1, 1), f"phase {name}: steps retraced {traces}")
    check(eng.overflow_pairs == 0, f"phase {name}: overflow under exact_moe")
    check(n_kernels > 0, f"phase {name}: decode step runs no Pallas kernel")
    check(counts["kept_full"] > 0, f"phase {name}: no sub-pair was kept")
    if policy != "none":
        check(counts["dropped"] + counts["kept_major"] > 0,
              f"phase {name}: 2T-Drop dropped nothing")


def check_moe_layer(name, cfg, params, policy, seed, dev) -> None:
    """Layer 0's MoE through the served kernel path vs the f32 dense
    reference on the same routed pairs."""
    import jax
    import jax.numpy as jnp
    from repro.core import moe
    from repro.data.pipeline import calibration_activations

    layer = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = calibration_activations(jax.random.PRNGKey(seed + 1), 128,
                                cfg.d_model).astype(jnp.bfloat16)
    policy = dataclasses.replace(policy, exact_capacity=True)
    pairs = jax.jit(lambda p, xx: policy.route(p, xx, cfg))(layer, x)

    def served(p, xx, pr):
        return moe.moe_forward_dispatch(
            p, xx, cfg, pairs=pr, capacity=xx.shape[0],
            use_kernel=policy.use_kernel,
            mode_grouped=policy.kernel_mode_grouping, fused_pipeline=True)

    served = jax.jit(served)
    n_kernels = served.lower(layer, x, pairs).compile().as_text().count(
        "tpu_custom_call")
    y = np.asarray(served(layer, x, pairs), np.float32)
    with jax.default_matmul_precision("highest"):
        layer32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
        y_ref = np.asarray(jax.jit(lambda p, xx, pr: moe.moe_forward_ref(
            p, xx, cfg, pairs=pr))(layer32, x.astype(jnp.float32), pairs))
    rel = float(np.linalg.norm(y - y_ref) / np.linalg.norm(y_ref))
    kept = int(np.asarray(pairs.keep).sum())
    print(f"phase {name}: MoE layer 0 on {dev.device_kind}, 128 tokens, "
          f"{kept}/{pairs.keep.size} sub-pairs kept, kernel launches "
          f"{n_kernels}: rel L2 error vs f32 reference {rel:.3e} "
          f"(tolerance {MOE_LAYER_REL_TOL:.0e})")
    check(np.isfinite(y).all(), f"phase {name}: non-finite MoE output")
    check(n_kernels > 0, f"phase {name}: MoE layer ran no Pallas kernel")
    check(rel <= MOE_LAYER_REL_TOL,
          f"phase {name}: MoE layer error {rel:.3e} above tolerance")


# ---------------------------------------------------------------------------
# four chips: S-ETP prefill vs one-chip dispatch prefill
# ---------------------------------------------------------------------------

def _parity_order(n_experts: int) -> np.ndarray:
    return np.concatenate([np.arange(0, n_experts, 2),
                           np.arange(1, n_experts, 2)])


def _as_dispatch_layout(moe_params, n_dev: int, p: int):
    """S-ETP expert layout -> an equivalent single-device dispatch layout.

    Undo the strided placement (placed[d*L + loc] = w[loc*D + d]), then
    reorder ORIGINAL experts even-first. With P=2 sub-experts strided over
    4 devices, an expert's halves sit on devices 2(e%2) and 2(e%2)+1, whose
    pre-drop loads are both the count of pairs routed to experts of e's
    parity; the load-aware step-down therefore depends only on the parity
    of e. Even-first order makes parity a contiguous 2-block layout, which
    is exactly what ``LoadAwareTwoT(n_devices=2)`` models on the dispatch
    path: the same thresholds for every pair."""
    import jax.numpy as jnp
    order = _parity_order(moe_params["wg"].shape[-1])
    sub_order = (order[:, None] * p + np.arange(p)[None, :]).reshape(-1)
    out = dict(moe_params)
    out["wg"] = moe_params["wg"][:, :, order]
    for k in ("w1", "w3", "w2"):
        w = moe_params[k]                          # (layers, E*P, ...)
        n_l, n_sub = w.shape[:2]
        w = w.reshape(n_l, n_dev, n_sub // n_dev, *w.shape[2:])
        w = jnp.swapaxes(w, 1, 2).reshape(n_l, n_sub, *w.shape[3:])
        out[k] = w[:, sub_order]
    return out


def four_chips(cfg, devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from repro.core.policy import make_policy
    from repro.data.pipeline import calibration_activations
    from repro.distributed.sharding import tree_shardings
    from repro.models import model as M
    from repro.models import transformer
    from repro.models.transformer import DistContext

    n_dev, batch, seq = 4, 4, 256
    mesh = jax.make_mesh((1, n_dev), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    policy = make_policy("load_aware", cfg.dualsparse)
    p = policy.partition_p
    key = jax.random.PRNGKey(seed)
    calib = calibration_activations(jax.random.fold_in(key, 7), 512,
                                    cfg.d_model).astype(jnp.bfloat16)

    # created sharded: the drawn tree is pinned to its sharding before the
    # partition, reconstruction and strided placement run, so no chip ever
    # holds the whole expert stack
    init_shapes, axes = M.abstract_params_and_axes(cfg, jnp.bfloat16)
    init_shardings = tree_shardings(axes, init_shapes, mesh)

    def build(k, c):
        params = jax.lax.with_sharding_constraint(
            M.init_params(k, cfg, dtype=jnp.bfloat16), init_shardings)
        return policy.prepare(params, cfg, c, n_ep_devices=n_dev)[0]

    shardings = tree_shardings(axes, jax.eval_shape(build, key, calib), mesh)
    t0 = time.perf_counter()
    params = jax.jit(build, out_shardings=shardings)(key, calib)
    jax.block_until_ready(params)
    print(f"S-ETP params: {_n_params(params)} on a (1, {n_dev}) mesh of "
          f"{devices[0].device_kind}, built sharded in "
          f"{time.perf_counter() - t0:.1f}s; policy load_aware "
          f"t_max={policy.t_max} t_gap={policy.t_gap} P={p}")
    experts = [params["blocks"]["moe"][k] for k in ("w1", "w3", "w2")]
    total = sum(w.nbytes for w in experts)
    for d in devices:
        held = sum(s.data.nbytes for w in experts
                   for s in w.addressable_shards if s.device == d)
        print(f"device {d.id}: expert bytes {held} of {total} "
              f"({held / total:.3f}), bytes_in_use "
              f"{_memory(d)['bytes_in_use']}")
        check(abs(held / total - 1 / n_dev) < 1e-6,
              f"device {d.id} holds {held / total:.3f} of the experts")

    tokens = jax.random.randint(jax.random.fold_in(key, 11), (batch, seq),
                                0, cfg.vocab_size)
    # exact capacity on both paths: S-ETP seats every kept pair (no
    # overflow), as the dispatch reference does
    policy = dataclasses.replace(policy, exact_capacity=True)
    setp = DistContext(mesh=mesh, moe_impl="setp", policy=policy)

    def prefill(prm, tok, dist):
        logits, cache = transformer.prefill(prm, {"tokens": tok}, cfg,
                                            dist=dist)
        return logits, cache["metrics"]

    step = jax.jit(lambda prm, tok: prefill(prm, tok, setp))
    with jax.set_mesh(mesh):
        tok_sharded = jax.device_put(tokens, NamedSharding(mesh, P()))
        t0 = time.perf_counter()
        compiled = step.lower(params, tok_sharded).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()
        logits, stats = compiled(params, tok_sharded)
        logits = np.asarray(logits, np.float32)
    n_a2a = hlo.count(" all-to-all(") + hlo.count(" all-to-all-start(")
    s = stats.snapshot()
    print(f"S-ETP prefill {batch}x{seq} on {n_dev} x "
          f"{devices[0].device_kind}: compile {compile_s:.1f}s (smoke "
          f"timing); all-to-all ops in compiled HLO: {n_a2a} (static count, "
          f"layer loop body counted once); kept_full={int(s['kept_full'])} "
          f"kept_major={int(s['kept_major'])} "
          f"dropped={int(s['dropped_pairs'])} "
          f"overflow={int(s['overflow_pairs'])}")
    check(n_a2a > 0, "S-ETP prefill compiled without an all-to-all")
    check(int(s["overflow_pairs"]) == 0,
          "S-ETP overflowed under exact capacity")
    check(np.isfinite(logits).all(), "S-ETP logits not finite")

    # the reference: the same weights, gathered onto one chip in the
    # equivalent dispatch layout, under the same load-aware thresholds
    one = SingleDeviceSharding(devices[0])
    blocks = dict(params["blocks"])
    moe_placed = blocks.pop("moe")
    relayout = jax.jit(lambda m: _as_dispatch_layout(m, n_dev, p),
                       out_shardings=jax.tree.map(lambda a: a.sharding,
                                                  moe_placed))
    ref_params = jax.device_put({**params, "blocks": blocks}, one)
    ref_params["blocks"]["moe"] = jax.device_put(relayout(moe_placed), one)
    del params, moe_placed, experts
    ref_policy = dataclasses.replace(policy, n_devices=2)
    single = DistContext(mesh=jax.make_mesh((1, 1), ("data", "model"),
                                            axis_types=(AxisType.Auto,) * 2,
                                            devices=devices[:1]),
                         moe_impl="dispatch", policy=ref_policy)
    ref_logits, ref_stats = jax.jit(lambda prm, tok: prefill(
        prm, tok, single))(ref_params, jax.device_put(tokens, one))
    ref_logits = np.asarray(ref_logits, np.float32)
    r = ref_stats.snapshot()
    rel = float(np.linalg.norm(logits - ref_logits)
                / np.linalg.norm(ref_logits))
    agree = float((logits.argmax(-1) == ref_logits.argmax(-1)).mean())
    print(f"one-chip dispatch reference: kept_full={int(r['kept_full'])} "
          f"kept_major={int(r['kept_major'])} "
          f"dropped={int(r['dropped_pairs'])}")
    print(f"S-ETP vs one chip: logits rel L2 error {rel:.3e} (tolerance "
          f"{DIST_LOGITS_REL_TOL:.0e}), greedy argmax agrees at "
          f"{agree:.4f} of {batch * seq} positions (minimum "
          f"{DIST_ARGMAX_AGREE_MIN})")
    check(rel <= DIST_LOGITS_REL_TOL, "S-ETP logits off the reference")
    check(agree >= DIST_ARGMAX_AGREE_MIN, "S-ETP argmax off the reference")


if __name__ == "__main__":
    main()
