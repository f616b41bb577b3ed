"""Record the small profiler trace, and the compiled programs' op_name
maps, that the scope tests read.

    python bench/record_scope_fixture.py [--out bench/fixtures/engine_scopes]

Runs Qwen3-30B-A3B's widths (2 layers, a 512-token vocabulary, seeded
weights) through ``PagedEngine`` on the TPU under the JAX profiler, with
the benchmark's own host annotations around each submit and step:
prompts of 40, 20 and 70 tokens on two slots, so a step holds a prefill
chunk beside decode and a request waits in the queue. At these widths
the device time is the layers' own, as in the chat cells. Writes
``<out>.xplane.pb.gz`` and ``<out>.op_names.json`` (program ->
instruction -> op_name, from ``decode_hlo()`` and ``chunk_hlo()``), and
prints device seconds by scope. Needs a TPU; exits 2 without one.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import gzip
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "bench", "fixtures", "engine_scopes"))
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_scope_fixture: no TPU", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from bench import scopes, xplane
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving import GenerationConfig, PagedEngine

    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b"), n_layers=2,
                              vocab_size=512)
    params = M.init_params(jax.random.PRNGKey(0), cfg,
                           dtype=jax.numpy.bfloat16)
    eng = PagedEngine(cfg, params, n_slots=2, page_size=16, chunk_size=32,
                      max_prompt_len=96, max_new_tokens=8)
    rng = np.random.default_rng(0)
    gen = GenerationConfig(max_new_tokens=8)
    eng.submit(rng.integers(0, 512, 40).astype(np.int32), gen)
    eng.drain()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            for n in (40, 20, 70):
                with jax.profiler.TraceAnnotation("bench_submit"):
                    eng.submit(rng.integers(0, 512, n).astype(np.int32), gen)
            while True:
                with jax.profiler.TraceAnnotation("bench_step"):
                    more = eng.step()
                if not more:
                    break
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        with open(path, "rb") as fh:
            raw = fh.read()
    maps = dict(scopes.op_names(t)
                for t in (eng.decode_hlo(), eng.chunk_hlo()))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out + ".xplane.pb.gz", "wb") as fh:
        fh.write(raw)
    with open(args.out + ".op_names.json", "w") as fh:
        json.dump(maps, fh, indent=0, sort_keys=True)
    print(f"wrote {args.out}.xplane.pb.gz and {args.out}.op_names.json")

    tr = xplane.load(args.out + ".xplane.pb.gz")
    win = xplane.loop_window(tr)
    for prog, secs in sorted(scopes.device_by_scope(tr, win, maps).items()):
        print(prog, {k: round(v * 1e3, 3) for k, v in sorted(secs.items())},
              "ms")
    print("idle by span:", xplane.idle_by_span(tr, win))


if __name__ == "__main__":
    main()
