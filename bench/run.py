#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``workloads`` in ``BENCHMARK.json``) is a model configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``), with its own fixed numbers in
``bench/cells/<cell>.json`` (the open-loop rate, the limits of the
correctness check). The configuration's ``model_type`` names its family
module (``bench/families/<model_type>.py``): its sizes, weight tree,
program configuration, reference layers and work counts.

Set-up (timed as ``setup_s`` from process start): seeded bf16 weights made
on the chip; for a 2T-Drop mix, calibration activations from the
reference's forward over seeded prompts, then the program's partition,
reconstruction and per-layer thresholds; the ``PagedEngine``; one request
through both jitted steps so every shape is compiled. Then ``--seconds``
of traffic through ``submit``/``step``. ``--trace 0`` prints the cell's
end-to-end metrics; ``--trace 1`` profiles a few seconds in the middle of
the window and prints its per-layer metrics. After the window the
program's state is freed, the weights are drawn again, and the float32
reference (the family's ``Reference`` on ``bench/reference.py``) is run
over a seeded sample of the finished requests: every served token's logit
must lie within the cell's limit of the reference's best. The last line
of standard output is one JSON object; the numbers compared, each beside
its limit, are the last lines of standard error and the result's last
key.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.traffic.generator import rng  # noqa: E402

TRACE_START = 0.5        # the traced part starts halfway through the window
TRACE_MAX_S = 6.0        # and lasts at most this long


# ---------------------------------------------------------------------------
# The cell, from data files
# ---------------------------------------------------------------------------

def _json(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    mix: Dict
    fixed: Dict                    # bench/cells/<cell>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]
    family: object                 # bench/families/<model_type>.py


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    config = _json(os.path.join(root, conf["file"]))
    return Cell(name=name, chips=w["chips"], config_name=w["config"],
                config=config, traffic_name=w["traffic"],
                mix=_json(os.path.join(root, "bench", "traffic",
                                       w["traffic"] + ".json")),
                fixed=_json(os.path.join(root, "bench", "cells",
                                         name + ".json")),
                end_to_end=e2e, per_layer=per, family=family(config, root))


def family(config: Dict, root: str = ROOT):
    """The family module of a configuration, found by its ``model_type``:
    ``bench/families/<model_type>.py`` under ``root``."""
    kind = config.get("model_type")
    path = os.path.join(root, "bench", "families", f"{kind}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no benchmark family for model_type {kind!r}: "
                         f"looked for {path}")
    name = f"bench.families.{kind}"
    mod = sys.modules.get(name)
    if mod is None or os.path.abspath(mod.__file__) != os.path.abspath(path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Device guard and compile cache
# ---------------------------------------------------------------------------

def require_chips(n: int):
    """The chips of the accelerator, or exit non-zero: never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform!r}); this "
              "benchmark measures the chip only", file=sys.stderr)
        sys.exit(3)
    if len(devs) < n:
        print(f"bench: the cell needs {n} chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(3)
    return devs[:n]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    a fixed directory inside the checkout. Every program is cached, however
    quickly it compiled, so a run's set-up after the first compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Counts the programs JAX looked up in its persistent cache: found
    there (``hits``) or compiled anew (``misses``)."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.n = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.n[self.EVENTS[event]] += 1

    def since(self, before: Dict[str, int]) -> str:
        return ", ".join(f"{v - before.get(k, 0)} {k}"
                         for k, v in self.n.items())



# ---------------------------------------------------------------------------
# Traced-run accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What the per-layer readers read (see ``bench/metrics``)."""
    family: object               # the configuration's family module
    s: Dict                      # model sizes
    p: int                       # sub-experts per expert in the served weights
    peaks: Dict
    steps: List[Dict]            # traced step records (bench/work.py)
    counts: List[int]            # kept_full, kept_major, dropped (traced)
    programs: Dict               # program name -> (device s, calls)
    kernel: tuple                # (device ns, calls) of Pallas kernels
    busy_s: float
    window_s: float


class Tracer:
    """Profiles the part of the window from ``t0`` to ``t1`` (seconds on
    the loop's clock), between whole steps, and keeps a record of every
    step in it: its wall time, what it decoded and prefilled, and the
    (sub-)experts it routed to (from device copies of the program's
    expert-load counters taken after each step)."""

    def __init__(self, engine, t0: float, t1: float, logdir: str):
        import jax
        self.engine = engine
        self.t0, self.t1 = t0, t1
        self.logdir = logdir
        self.state = "waiting"
        self.steps: List[Dict] = []
        self.snaps: List = []
        self._copy = jax.jit(lambda m: jax.tree.map(lambda a: a + 0, m))
        self._before = None

    def warm(self):
        self._copy(self.engine._device_metrics())

    def _slots(self):
        return {st.uid: (st.next_start, len(st.prompt))
                for st in self.engine._slots if st is not None}

    def __call__(self, phase: str, loop) -> None:
        import jax
        if phase == "before":
            if self.state == "waiting" and loop.now() >= self.t0:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.logdir, profiler_options=opts)
                self.snaps.append(self._copy(self.engine._device_metrics()))
                self.state = "tracing"
            if self.state == "tracing":
                self._before = (
                    {uid: len(s.stamps) for uid, s in loop.live.items()},
                    self._slots(), self.engine.prefill_tokens)
            return
        if self.state != "tracing":
            return
        self.snaps.append(self._copy(self.engine._device_metrics()))
        self.steps.append(self._record(loop))
        if loop.now() >= self.t1:
            self.close()

    def close(self) -> None:
        """Stop the profiler if the traced part is still open."""
        import jax
        if self.state == "tracing":
            np.asarray(self.snaps[-1].kept_full)
            jax.profiler.stop_trace()
            self.state = "done"

    def _record(self, loop) -> Dict:
        counts, slots, n_pref = self._before
        step = loop.steps[-1]
        keys = []
        for s in loop.served:
            n0 = counts.get(s.uid)
            if n0 is None:
                continue
            n1 = len(s.stamps)
            plen = len(s.req.prompt)
            before = max(n0, 1)            # a first token comes from a chunk
            n_dec = n1 - n0 - (1 if n0 == 0 and n1 > 0 else 0)
            keys += [plen + before + i for i in range(n_dec)]
        chunk = None
        valid = self.engine.prefill_tokens - n_pref
        if valid:
            after = self._slots()
            for uid, (nxt, plen) in after.items():
                start0 = slots.get(uid, (None,))[0]
                if start0 is not None and nxt - start0 == valid:
                    chunk = (start0, valid, nxt == plen)
                    break
            else:
                for uid, (nxt, plen) in after.items():
                    if uid not in slots and nxt >= valid:
                        chunk = (nxt - valid, valid, nxt == plen)
                        break
        return {"wall_s": step.t1 - step.t0, "decode_keys": keys,
                "chunk": chunk}

    def finish(self, fam, s: Dict, sizes_p: int, peaks: Dict) -> tuple:
        """(Context, breakdown, busy_s, window_s) from the trace."""
        from bench import xplane
        loads = [np.asarray(m.expert_load) for m in self.snaps]
        kept = [np.array([int(m.kept_full), int(m.kept_major),
                          int(m.dropped_pairs)]) for m in self.snaps]
        for i, rec in enumerate(self.steps):
            rec["live"] = (loads[i + 1] - loads[i]) > 0
        counts = [int(x) for x in kept[-1] - kept[0]]
        paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = xplane.load(paths[0])
        win = xplane.loop_window(tr)
        window_s = (win[1] - win[0]) * 1e-9
        busy_s = xplane.busy_ns(tr, win) * 1e-9
        ctx = Context(family=fam, s=s, p=sizes_p, peaks=peaks,
                      steps=self.steps, counts=counts,
                      programs=xplane.programs(tr, win),
                      kernel=xplane.kernel_ns(tr, win), busy_s=busy_s,
                      window_s=window_s)
        idle = sorted(xplane.idle_by_span(tr, win).items(),
                      key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[n, v] for n, v in
                                    xplane.top_ops(tr, win)],
                     "idle_gaps": [[n, v] for n, v in idle]}
        return ctx, breakdown, busy_s, window_s


# ---------------------------------------------------------------------------
# End-to-end metrics
# ---------------------------------------------------------------------------

def end_to_end(win, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric the window supports (the cell keeps its
    own)."""
    out = {"setup_s": setup_s}
    arrived = win.in_window()
    if arrived:
        ttft = [(s.stamps[0] if s.stamps else win.end_s) - s.due_s
                for s in arrived]
        out["ttft_p90_ms"] = 1e3 * float(np.percentile(ttft, 90))
    gaps = [b - a for s in win.served
            for a, b in zip(s.stamps, s.stamps[1:]) if b <= win.seconds]
    if gaps:
        out["itl_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
    out["offline_tok_s"] = ((win.prefill_in_window()
                             + win.tokens_in_window()) / win.seconds)
    return out


# ---------------------------------------------------------------------------
# Correctness: served tokens against the float32 reference
# ---------------------------------------------------------------------------

def sample_finished(win, n: int, seed: int):
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [s for s in win.served if s.done]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.req.prompt) + len(s.stamps))
    rest = [s for s in done if s is not longest]
    pick = rng(seed, 3).choice(len(rest), size=min(n - 1, len(rest)),
                               replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


NO_SAMPLE = 1e9          # what a gap reads when no request finished

# What the served tokens' gaps are reduced to. A cell compares those its
# ``bench/cells/<cell>.json`` gives a limit.
GAP_STATS = {"mean_gap": np.mean, "widest_gap": np.max}


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    return {k: float(f(gaps)) if len(gaps) else NO_SAMPLE
            for k, f in GAP_STATS.items()}


def judge(stats: Dict[str, float], limits: Dict, overflow: int,
          bad_ids: int, dropped: Optional[int] = None) -> Dict[str, Dict]:
    """The numbers compared, each with its limit. ``dropped``, the pairs
    the program dropped, is given where the cell's policy drops none."""
    checks = {name: {"value": stats[name], "limit": limit}
              for name, limit in limits.items()}
    checks["overflow_pairs"] = {"value": overflow, "limit": 0}
    if dropped is not None:
        checks["dropped_pairs"] = {"value": dropped, "limit": 0}
    checks["token_ids_outside_vocab"] = {"value": bad_ids, "limit": 0}
    return checks


def passes(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def check(sample, tokens_of, ref, limits: Dict, overflow: int,
          vocab: int, control=None, dropped: Optional[int] = None) -> tuple:
    """The numbers compared, each with its limit; the served tokens' gap
    statistics and how many tokens they cover; and, given the ``control``
    reference, the same statistics of the tokens the control would have
    put first at the same positions (else None)."""
    from bench.reference import served_gaps
    gaps, ctrl = [], []
    bad_ids = 0
    for s in sample:
        out = np.asarray(tokens_of(s), np.int32)
        bad_ids += int(np.sum((out < 0) | (out >= vocab)))
        out = np.clip(out, 0, vocab - 1)
        g, c = served_gaps(ref, s.req.prompt, out, control)
        gaps.append(g)
        if c is not None:
            ctrl.append(c)
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    stats = gap_stats(gaps)
    ctrl_stats = None
    if control is not None:
        ctrl_stats = gap_stats(np.concatenate(ctrl) if ctrl else np.zeros(0))
    return (judge(stats, limits, overflow, bad_ids, dropped), stats,
            len(gaps), ctrl_stats)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def calib_tokens(mix: Dict, seed: int, vocab: int) -> List[np.ndarray]:
    c = mix["calibration"]
    r = rng(seed, 2)
    return [r.integers(0, vocab, c["prompt_len"]).astype(np.int32)
            for _ in range(c["prompts"])]


@dataclasses.dataclass
class Built:
    eng: object
    s: Dict
    policy: object
    calib: Optional[np.ndarray]


def build(cell: Cell, seed: int) -> Built:
    """Weights, policy and a warmed-up engine for ``cell`` at ``seed``."""
    import jax
    import jax.numpy as jnp
    from bench import system, weights

    fam = cell.family
    s = fam.sizes(cell.config)
    mc = fam.model_config(cell.config_name, cell.config, s)
    mix = cell.mix
    params = jax.block_until_ready(weights.make(fam.layout(s), seed))
    system.check_tree(mc, params)
    policy = system.policy_of(mc, mix)
    calib = None
    if policy is not None:
        calib = fam.Reference(params, s).calibration(
            calib_tokens(mix, seed, s["vocab"]))
    params, dist = system.apply_policy(mc, params, policy,
                                       None if calib is None
                                       else jnp.asarray(calib))
    eng = system.engine(mc, params, dist, mix)
    del params
    e = mix["engine"]
    warm = np.arange(min(e["chunk_size"] + 1, e["max_prompt_len"]),
                     dtype=np.int32) % s["vocab"]
    eng.submit(warm, system.gen_for(2))
    eng.drain()
    return Built(eng, s, policy, calib)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, *,
        control: bool = False, t_start: float = T_START,
        compiles: Optional[Compiles] = None, log=print) -> Dict:
    """One run of ``cell``; returns the result object. ``control`` also
    judges the control on the same sample against the same limits, and
    adds both sides' numbers to the result (for setting the limits);
    ``t_start`` is when set-up began; ``compiles``, where given, reports
    the programs set-up and the window took from the compile cache or
    compiled."""
    import jax
    from bench import system, weights
    from bench.peaks import peaks as peaks_of
    from bench.traffic.generator import generate
    from bench.traffic.loops import Loop

    dev = devices[0]
    mix = cell.mix
    fam = cell.family
    b = build(cell, seed)
    eng, s, policy, calib = b.eng, b.s, b.policy, b.calib
    del b
    requests = generate(mix, seed, seconds, s["vocab"],
                        rate_per_s=cell.fixed.get("rate_per_s", 0.0))
    tracer = None
    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory()
        t0 = TRACE_START * seconds
        tracer = Tracer(eng, t0, t0 + min(TRACE_MAX_S, 0.4 * seconds),
                        tmp.name)
        tracer.warm()
    jax.block_until_ready(eng._cache)
    setup_s = time.perf_counter() - t_start
    log(f"bench: {cell.name} seed {seed}: set-up {setup_s:.2f} s; "
        f"{len(requests)} requests generated", file=sys.stderr)
    if compiles is not None:
        log(f"bench: set-up programs: {compiles.since({})} in the compile "
            "cache", file=sys.stderr)
        at_window = dict(compiles.n)

    loop = Loop(eng, system.gen_for, around_step=tracer)
    if mix["loop"] == "open":
        win = loop.run_open(requests, seconds, mix["grace_s"])
    else:
        win = loop.run_closed(requests, mix["clients"], mix["stagger_s"],
                              seconds)
    if tracer is not None:
        tracer.close()
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    overflow = int(eng.overflow_pairs)
    # every pair computed whole where the policy drops none (the program's
    # counter, since set-up)
    dropped = (None if policy is not None
               else int(eng._device_metrics().dropped_pairs))
    late = max(win.late_s) if win.late_s else 0.0
    log(f"bench: window {seconds:.0f} s: {len(win.served)} requests sent, "
        f"{len(win.steps)} steps; generator at most {1e3 * late:.2f} ms "
        f"late; memory peak {peak} bytes", file=sys.stderr)
    if compiles is not None:
        log(f"bench: window programs: {compiles.since(at_window)} in the "
            "compile cache (none is compiled or loaded in the window)",
            file=sys.stderr)

    result: Dict = {}
    if trace:
        if tracer.state != "done":
            raise RuntimeError("the traced part of the window never closed")
        ctx, breakdown, busy_s, window_s = tracer.finish(
            fam, s, 1 if policy is None else policy.partition_p,
            peaks_of(dev.device_kind))
        tmp.cleanup()
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    attempted = (len(win.in_window()) if mix["loop"] == "open"
                 else len([x for x in win.served if x.sent_s < seconds]))
    failed = (sum(1 for x in win.in_window() if not x.stamps)
              if mix["loop"] == "open" else 0)
    results_of = {x.uid: eng.result(x.uid).tokens for x in win.served}

    # free the program's state before the reference takes the chip
    loop.engine = None
    del eng, loop, tracer
    gc.collect()

    params = jax.block_until_ready(weights.make(fam.layout(s), seed))
    two_t = None
    if policy is not None:
        two_t = fam.calibrate_2t(
            params, s, calib, p=policy.partition_p,
            importance=cell.config["dualsparse"]["importance"],
            drop_target=mix["policy"]["drop_target"],
            delta=mix["policy"]["delta"])
    ref = fam.Reference(params, s, two_t)
    sample = sample_finished(win, mix["check"]["sample_requests"], seed)
    t_ref = time.perf_counter()
    checks, stats, n_tok, ctrl = check(
        sample, lambda x: results_of[x.uid], ref, cell.fixed["limits"],
        overflow, s["vocab"], control=ref if control else None,
        dropped=dropped)
    log(f"bench: reference over {len(sample)} requests ({n_tok} served "
        f"tokens) in {time.perf_counter() - t_ref:.1f} s; gaps: " + ", ".join(
            f"{k} {v}" for k, v in stats.items()), file=sys.stderr)
    correct = passes(checks)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=busy_s, window_s=window_s)
    result.update(correct=bool(correct), attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if trace:
        result["breakdown"] = breakdown
    if control:
        result["program_gaps"] = stats
        result["served_tokens"] = n_tok
        result["control_gaps"] = ctrl
        ctrl_checks = judge(ctrl, cell.fixed["limits"], 0, 0)
        result["control_check"] = ctrl_checks
        result["control_correct"] = passes(ctrl_checks)
        for name, c in ctrl_checks.items():
            log(f"control {name}: {c['value']} (limit {c['limit']})",
                file=sys.stderr)
    result["check"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})",
            file=sys.stderr)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices,
                 compiles=Compiles(), log=print)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
