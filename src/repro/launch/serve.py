"""Serving driver: requests through the DualSparse-MoE serving engines.

Sparsity is selected with ``--policy`` (the SparsityPolicy registry):
  none       — plain top-k MoE
  1t         — 1T-Drop (all-or-nothing per token-expert pair)
  2t         — partition + reconstruction + 2T-Drop (paper §4.2)
  load_aware — 2T with load-aware per-device thresholds (§4.3)
  per_layer  — 2T with per-layer thresholds calibrated to --drop-target

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
      --reduced --requests 8 --prompt-len 64 --new-tokens 32 --policy 2t
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
      --reduced --engine continuous --slots 4 --requests 8 \
      --policy per_layer --drop-target 0.25
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.policy import POLICIES, make_policy
from repro.data.pipeline import SyntheticLM, calibration_activations
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import (ContinuousBatchingEngine, GenerationConfig,
                           PagedEngine, ServingEngine)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-moe-30b-a3b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", default="sync",
                    choices=("sync", "continuous", "paged"),
                    help="synchronized batches, slot-based continuous "
                         "batching with mid-decode admission, or paged KV "
                         "(page-table cache + chunked prefill + prefix cache)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=8,
                    help="sync batch size / continuous slot count")
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous/paged engine slot count "
                         "(0 = --batch-size)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged engine: tokens per KV page")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="paged engine: prompt tokens per prefill chunk")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged engine: disable cross-request prefix reuse")
    ap.add_argument("--policy", default=None, choices=sorted(POLICIES),
                    help="sparsity policy (default: none)")
    ap.add_argument("--drop-target", type=float, default=None,
                    help="calibrate policy thresholds to this drop rate on "
                         "synthetic calibration activations")
    ap.add_argument("--dualsparse", action="store_true",
                    help="DEPRECATED alias for --policy 2t")
    ap.add_argument("--fused-pipeline", action="store_true", default=None,
                    help="force MoE layers through the single fused "
                         "streamed Pallas dispatch->FFN->combine kernel "
                         "(no (E, C, d) HBM buffer, no unpermute "
                         "read-back). Default is AUTO: the per-shape "
                         "heuristic (core.dispatch.prefer_fused_pipeline) "
                         "picks fused wherever the bench shows a win — "
                         "always on TPU, with use_kernel on CPU")
    ap.add_argument("--no-fused-pipeline", dest="fused_pipeline",
                    action="store_false",
                    help="force the buffer path (disable the fused kernel "
                         "even where the heuristic would pick it)")
    ap.add_argument("--seed", type=int, default=0)
    # observability (repro.obs)
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable the traced on-device metrics seam "
                         "(cache falls back to the legacy moe_overflow "
                         "scalar)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text exposition on this port "
                         "while requests run (0 = ephemeral); the driver "
                         "self-scrapes /metrics at the end and fails if "
                         "the payload does not round-trip")
    ap.add_argument("--metrics-log", default=None, metavar="PATH",
                    help="append one JSON metrics snapshot line after the "
                         "run ('-' = stdout)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the engine span trace as Chrome-trace JSON "
                         "(load in chrome://tracing or Perfetto)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the whole run "
                         "into this directory (TensorBoard/XProf format)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg, dtype=jnp.bfloat16)

    policy_name = args.policy
    if policy_name is None and args.dualsparse:
        print("--dualsparse is deprecated; use --policy 2t")
        policy_name = "2t"
    policy_name = policy_name or "none"

    dist = None
    # an explicit --fused-pipeline/--no-fused-pipeline needs a policy object
    # to carry the hint, so it also builds one for --policy none
    force_dist = policy_name != "none" or args.fused_pipeline is not None
    if force_dist and cfg.is_moe and cfg.dualsparse.enabled:
        policy = make_policy(policy_name, cfg.dualsparse,
                             drop_target=args.drop_target,
                             fused_pipeline=args.fused_pipeline)
        calib = calibration_activations(jax.random.PRNGKey(7), 512,
                                        cfg.d_model)
        params, policy = policy.prepare(params, cfg, calib)
        from repro.models.transformer import DistContext
        from repro.launch.mesh import make_host_mesh
        # single-host: policy-driven dispatch path without shard_map
        dist = DistContext(mesh=make_host_mesh(1), moe_impl="dispatch",
                           policy=policy)
        print(f"sparsity policy {policy.name!r}: partition P="
              f"{policy.partition_p}"
              + (f", drop_target={args.drop_target}"
                 if args.drop_target is not None else ""))

    src = SyntheticLM(cfg.vocab_size, seed=args.seed)
    prompts = [np.asarray(src.sample_batch(
        jax.random.fold_in(key, i), 1, args.prompt_len)["tokens"][0])
        for i in range(args.requests)]

    metrics = not args.no_metrics
    trace = bool(args.trace_out)
    if args.engine == "continuous":
        eng = ContinuousBatchingEngine(
            cfg, params, n_slots=args.slots or args.batch_size,
            max_prompt_len=args.prompt_len, max_new_tokens=args.new_tokens,
            dist=dist, metrics=metrics, trace=trace)
    elif args.engine == "paged":
        eng = PagedEngine(
            cfg, params, n_slots=args.slots or args.batch_size,
            page_size=args.page_size, chunk_size=args.chunk_size,
            max_prompt_len=args.prompt_len, max_new_tokens=args.new_tokens,
            dist=dist, prefix_cache=not args.no_prefix_cache,
            metrics=metrics, trace=trace)
    else:
        eng = ServingEngine(cfg, params, batch_size=args.batch_size,
                            max_prompt_len=args.prompt_len,
                            max_new_tokens=args.new_tokens, dist=dist,
                            metrics=metrics, trace=trace)

    server = None
    if args.metrics_port is not None:
        from repro.obs import MetricsServer
        server = MetricsServer(eng.metrics, port=args.metrics_port)
        server.start()
        print(f"metrics: serving Prometheus exposition at {server.url}")
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.time()
    try:
        results = eng.generate(prompts, GenerationConfig(
            max_new_tokens=args.new_tokens, seed=args.seed))
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
            print(f"profiler trace written to {args.profile_dir}")
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in results)
    print(f"served {len(results)} requests, {n_tok} tokens "
          f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s) "
          f"policy={policy_name} moe_overflow={eng.overflow_pairs}")
    timing = eng.timing
    print(f"  compile={timing['compile_s']:.2f}s "
          f"({timing['compile_steps']} traced steps) "
          f"steady_step={timing['steady_step_s'] * 1e3:.1f}ms "
          f"over {timing['steady_steps']} steps")
    if args.engine == "continuous":
        print(f"  slots={eng.n_slots} admitted={eng.n_admitted} "
              f"decode_steps={eng.decode_steps} "
              f"max_concurrency={eng.max_concurrency} "
              f"traces(prefill={eng.prefill_traces}, "
              f"decode={eng.decode_traces})")
    elif args.engine == "paged":
        print(f"  slots={eng.n_slots} admitted={eng.n_admitted} "
              f"chunk_steps={eng.chunk_steps} "
              f"decode_steps={eng.decode_steps} "
              f"prefix_hit_rate={eng.prefix_hit_rate:.2f} "
              f"traces(chunk={eng.chunk_traces}, "
              f"decode={eng.decode_traces})")
    for r in results[:4]:
        print(f"  req{r.uid}: {r.tokens[:12]}...")

    if args.metrics_log:
        from repro.obs import snapshot_json_line
        line = snapshot_json_line(eng.metrics(), arch=args.arch,
                                  engine=args.engine, policy=policy_name)
        if args.metrics_log == "-":
            print(line)
        else:
            with open(args.metrics_log, "a") as f:
                f.write(line + "\n")
            print(f"metrics: snapshot appended to {args.metrics_log}")
    if args.trace_out:
        eng.tracer.write_chrome_trace(args.trace_out)
        print(f"metrics: span trace written to {args.trace_out} "
              f"({len(eng.tracer.events())} events)")
    if server is not None:
        import urllib.request
        from repro.obs import parse_prometheus
        with urllib.request.urlopen(server.url) as resp:
            text = resp.read().decode()
        snap = parse_prometheus(text)
        n_series = (len(snap.counters) + len(snap.gauges)
                    + len(snap.histograms))
        server.stop()
        if n_series == 0:
            raise SystemExit("metrics scrape FAILED: no series parsed")
        print(f"metrics scrape ok ({n_series} series)")


if __name__ == "__main__":
    main()
