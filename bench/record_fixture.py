"""Record the small profiler trace that the trace-reduction tests read.

    python bench/record_fixture.py [--out bench/fixtures/engine_trace.xplane.pb.gz]

Runs a tiny MoE model (the Qwen3-MoE layout at reduced widths) through
``PagedEngine`` on the TPU for a few steps under the JAX profiler, with the
benchmark's own host annotations around each step, and copies the
``.xplane.pb`` to ``--out``. It prints what the trace holds (planes, lines,
the most frequent event names) so the reduction's names can be checked.
Needs a TPU; exits 2 without one.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import glob
import gzip
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "bench", "fixtures", "engine_trace.xplane.pb.gz"))
    args = ap.parse_args()

    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving import GenerationConfig, PagedEngine

    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b").reduced(),
                              vocab_size=512)
    params = M.init_params(jax.random.PRNGKey(0), cfg, dtype=jax.numpy.bfloat16)
    eng = PagedEngine(cfg, params, n_slots=2, page_size=16, chunk_size=32,
                      max_prompt_len=64, max_new_tokens=8)
    rng = np.random.default_rng(0)
    gen = GenerationConfig(max_new_tokens=8)
    eng.submit(rng.integers(0, 512, 40).astype(np.int32), gen)
    eng.drain()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            for n in (40, 20):
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
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with (gzip.open if args.out.endswith(".gz") else open)(args.out,
                                                            "wb") as fh:
        fh.write(raw)
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes)")

    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            names = collections.Counter(e.name for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events; top "
                  f"{names.most_common(12)}")
            for e in events[:2]:
                print(f"    e {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} stats={dict(e.stats)}")


if __name__ == "__main__":
    main()
