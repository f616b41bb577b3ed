"""repro.lint: each pass family must catch its seeded violation, and the
repo as landed must come out clean on the fast entry set."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import SparsityPolicy, register_policy, POLICIES
from repro.kernels import (fused_moe_pipeline_kernel_spec,
                           grouped_swiglu_kernel_spec)
from repro.lint import Baseline, Finding, Severity, build_entries, run_lint
from repro.lint import bench_schema, hlo_passes, jaxpr_passes, pallas_passes

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# jaxpr family
# ---------------------------------------------------------------------------

def test_dtype_pass_catches_injected_f64():
    def bad(x):
        return jnp.cumsum(x.astype(jnp.float64))   # seeded upcast

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(bad)(
            jax.ShapeDtypeStruct((8,), jnp.float32))
    found = jaxpr_passes.check_dtype_promotion(jaxpr, "seeded")
    assert any(f.severity == Severity.ERROR and f.pass_name == "jaxpr-dtype"
               for f in found), found


def test_dtype_pass_catches_weak_type_promotion():
    """The pre-fix load_aware.py shape: dividing an integer histogram
    without an explicit f32 cast promotes to f64 under x64 — exactly what
    the f32 pinning in core.load_aware now prevents."""
    def leaky(scores):
        hist = jnp.arange(scores.shape[0])
        return hist / hist.size                    # i64/int -> f64 on x64

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(leaky)(
            jax.ShapeDtypeStruct((32,), jnp.float32))
    found = jaxpr_passes.check_dtype_promotion(jaxpr, "seeded")
    assert found, "weak-type promotion went undetected"


def test_calibration_entries_clean_under_x64():
    """core.drop / core.load_aware calibration math is f32-explicit: the
    x64 probe entries produce zero dtype findings (the satellite fix)."""
    entries = [e for e in build_entries(include_hlo=False,
                                        include_engine=False)
               if e.name.startswith("calib/")]
    assert len(entries) == 2
    for e in entries:
        art = e.trace()
        assert jaxpr_passes.check_dtype_promotion(art.jaxpr, e.name) == []


def test_host_sync_pass_catches_debug_print():
    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    jaxpr = jax.make_jaxpr(chatty)(jax.ShapeDtypeStruct((4,), jnp.float32))
    found = jaxpr_passes.check_host_sync(jaxpr, "seeded")
    assert any(f.pass_name == "jaxpr-hostsync" for f in found)


def test_host_sync_pass_catches_pure_callback():
    def roundtrip(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) * 2, jax.ShapeDtypeStruct((4,),
                                                              np.float32), x)

    jaxpr = jax.make_jaxpr(roundtrip)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    found = jaxpr_passes.check_host_sync(jaxpr, "seeded")
    assert any(f.severity == Severity.ERROR for f in found)


def test_traced_leaves_pass_accepts_argument_and_flags_constant():
    """The page-table retrace-hazard check: an int32 indirection array
    passed as an argument is clean; the same array captured as a closure
    constant (whose VALUE would hash into the jit cache key) is an ERROR."""
    table = jnp.zeros((2, 5), jnp.int32)
    spec = [[(2, 5), "int32"]]

    def good(x, pt):
        return jnp.take(x, pt.reshape(-1), axis=0)

    jaxpr = jax.make_jaxpr(good)(jax.ShapeDtypeStruct((8, 4), jnp.float32),
                                 jax.ShapeDtypeStruct((2, 5), jnp.int32))
    assert jaxpr_passes.check_traced_leaves(jaxpr, "seeded", spec) == []

    def bad(x):
        return jnp.take(x, table.reshape(-1), axis=0)   # captured constant

    jaxpr = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((8, 4), jnp.float32))
    found = jaxpr_passes.check_traced_leaves(jaxpr, "seeded", spec)
    assert any(f.fingerprint ==
               "jaxpr-traced-leaves:leaf-captured-constant:seeded"
               for f in found), found

    missing = jaxpr_passes.check_traced_leaves(jaxpr, "seeded",
                                               [[(3, 7), "int32"]])
    assert any(f.pass_name == "jaxpr-traced-leaves"
               and "leaf-missing" in f.fingerprint for f in missing)


def test_paged_engine_entries_trace_clean():
    """The paged serving steps take the page table as a traced invar (no
    captured constants) and carry the traced_leaves meta the runner keys
    the check on."""
    entries = [e for e in build_entries(include_hlo=False)
               if e.name in ("engine/chunk_insert", "engine/paged_decode",
                             "engine/prefix_hit_insert")]
    assert len(entries) == 3
    for e in entries:
        assert e.meta.get("traced_leaves")
        art = e.trace()
        assert jaxpr_passes.check_traced_leaves(
            art.jaxpr, e.name, e.meta["traced_leaves"]) == []


# ---------------------------------------------------------------------------
# policy retrace-hazard family
# ---------------------------------------------------------------------------

def _register_throwaway(cls, name):
    register_policy(name)(cls)
    POLICIES.pop(name, None)           # keep the production registry clean
    return cls


def test_retrace_pass_flags_unhashable_static():
    @dataclasses.dataclass(frozen=True)
    class ListStatic(SparsityPolicy):
        knobs: Tuple = dataclasses.field(default_factory=lambda: [1, 2])
        _dynamic: Tuple[str, ...] = ()

        @classmethod
        def from_config(cls, ds, drop_target=None, **kw):
            return cls(**kw)

    _register_throwaway(ListStatic, "__lint_unhashable")
    found = jaxpr_passes.check_policy_retrace({"bad": ListStatic})
    assert any(f.code == "unhashable-static" for f in found), found


def test_retrace_pass_flags_array_valued_static():
    @dataclasses.dataclass(frozen=True)
    class ArrayStatic(SparsityPolicy):
        table: Tuple = dataclasses.field(
            default_factory=lambda: np.zeros(3))
        _dynamic: Tuple[str, ...] = ()      # table SHOULD be dynamic

        @classmethod
        def from_config(cls, ds, drop_target=None, **kw):
            return cls(**kw)

    _register_throwaway(ArrayStatic, "__lint_arraystatic")
    found = jaxpr_passes.check_policy_retrace({"bad": ArrayStatic})
    assert any(f.code == "traced-value-hashed" for f in found), found


def test_retrace_pass_flags_phantom_dynamic_field():
    @dataclasses.dataclass(frozen=True)
    class Phantom(SparsityPolicy):
        _dynamic: Tuple[str, ...] = ("no_such_field",)

        @classmethod
        def from_config(cls, ds, drop_target=None, **kw):
            return cls(**kw)

    # NOT registered: register_policy would raise on flatten; the pass must
    # diagnose rather than crash
    found = jaxpr_passes.check_policy_retrace({"bad": Phantom})
    assert any(f.code == "dynamic-not-a-field" for f in found), found


def test_retrace_pass_clean_on_production_registry():
    assert jaxpr_passes.check_policy_retrace() == []


# ---------------------------------------------------------------------------
# HLO family
# ---------------------------------------------------------------------------

def test_capacity_buffer_pass_catches_injected_materialization():
    E, cap, d = 4, 64, 32

    def leaky(x):
        buf = jnp.broadcast_to(x[None, None, :], (E, cap, d)) * 2.0
        return buf.sum()

    hlo = jax.jit(leaky).lower(
        jax.ShapeDtypeStruct((d,), jnp.float32)).compile().as_text()
    found = hlo_passes.check_forbidden_shapes(hlo, "seeded", [(E, cap, d)])
    assert any(f.code == "forbidden-shape" and
               f.severity == Severity.ERROR for f in found), found
    # and the converse guard sees it too
    assert hlo_passes.check_required_shapes(hlo, "seeded",
                                            [(E, cap, d)]) == []
    assert hlo_passes.check_required_shapes(hlo, "seeded",
                                            [(E, cap + 1, d)]) != []


def test_capacity_buffer_count_matches_bench_semantics():
    """capacity_buffer_count (the helper bench_moe_pipeline now imports)
    counts both the exact and the block-padded capacity layouts."""
    E, cap, d = 2, 200, 16

    def f(x):
        a = jnp.broadcast_to(x, (E, cap, d)) * 1.5
        b = jnp.broadcast_to(x, (E, 256, d)) + 1.0   # padded-to-128 layout
        return a.sum() + b.sum()

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((d,), jnp.float32)).compile().as_text()
    n_both = hlo_passes.capacity_buffer_count(hlo, E, cap, d, block_c=128)
    n_exact = hlo_passes.capacity_buffer_count(hlo, E, cap, d, block_c=cap)
    assert n_both > n_exact > 0


_SYNTH_A2A = """\
ENTRY %main (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16] parameter(0)
  %a = f32[8,16] all-to-all(%p), dimensions={0}
  %b = f32[8,16] all-to-all(%a), dimensions={0}
  %c = f32[8,16] all-to-all(%b), dimensions={0}
  %g = f32[8,16] all-gather(%c), dimensions={0}
  ROOT %r = f32[8,16] add(%g, %p)
}
"""


def test_collective_budget_pass():
    found = hlo_passes.check_collective_budget(
        _SYNTH_A2A, "seeded", {"all-to-all": 2, "all-gather": 0})
    codes = {f.code for f in found}
    assert codes == {"budget-all-to-all", "budget-all-gather"}, found
    assert hlo_passes.check_collective_budget(
        _SYNTH_A2A, "seeded", {"all-to-all": 3, "all-gather": 1}) == []


def test_hbm_bytes_regression_gate():
    def f(x):
        return (x @ x.T).sum()

    hlo = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile().as_text()
    from repro.launch.hlo_analysis import analyze_hlo
    actual = analyze_hlo(hlo).hbm_bytes
    assert hlo_passes.check_hbm_bytes(hlo, "e", actual) == []
    assert any(f.code == "no-baseline"
               for f in hlo_passes.check_hbm_bytes(hlo, "e", None))
    regress = hlo_passes.check_hbm_bytes(hlo, "e", actual / 10)
    assert any(f.code == "regression" and f.severity == Severity.ERROR
               for f in regress)


# ---------------------------------------------------------------------------
# Pallas family
# ---------------------------------------------------------------------------

def test_vmem_pass_streamed_prefill_passes_unstreamed_fails():
    """The satellite-1 regression pair: at prefill scale (T=8192) the
    STREAMED spec (pair maps in SMEM, x/out in ANY memory behind DMA)
    fits the 16 MB budget, while the deliberately unstreamed (resident)
    layout still blows it — deleting the old prod_prefill suppression
    must never silently re-admit a resident prefill kernel."""
    kw = dict(capacity=2048, dtype=jnp.bfloat16, p_factor=2)
    T, n_pairs = 8192, 8192 * 8 + 128
    ok = fused_moe_pipeline_kernel_spec(T, 2048, 384, 128, n_pairs,
                                        streamed=True, **kw)
    assert pallas_passes.check_vmem_footprint(ok, "streamed") == []
    # acceptance shape: wide model (d=4096, E=64) at T=8192 also fits
    wide = fused_moe_pipeline_kernel_spec(T, 4096, 7168, 64, 8192 * 2 + 128,
                                          streamed=True, capacity=1024,
                                          dtype=jnp.bfloat16, p_factor=2)
    assert pallas_passes.check_vmem_footprint(wide, "streamed-wide") == []
    bad = fused_moe_pipeline_kernel_spec(T, 2048, 384, 128, n_pairs,
                                         streamed=False, **kw)
    found = pallas_passes.check_vmem_footprint(bad, "resident")
    assert any(f.code == "vmem-budget" and f.severity == Severity.ERROR
               for f in found), found


def test_vmem_pass_passes_decode_scale():
    for streamed in (True, False):
        spec = fused_moe_pipeline_kernel_spec(
            256, 2048, 384, 128, 256 * 16 + 128, capacity=64,
            dtype=jnp.bfloat16, p_factor=2, streamed=streamed)
        assert pallas_passes.check_vmem_footprint(spec, "ok") == []


def test_smem_pass_budget_and_clean():
    # mode-grouped prefill maps fit SMEM
    ok = fused_moe_pipeline_kernel_spec(
        8192, 2048, 384, 128, 8192 * 8 + 128, capacity=2048,
        dtype=jnp.bfloat16, p_factor=2)
    assert pallas_passes.check_smem_footprint(ok, "ok") == []
    # a raw sub-pair layout at prefill scale (T*top_k*P entries) does not
    big = fused_moe_pipeline_kernel_spec(
        16384, 2048, 384, 128, 16384 * 8 * 2 + 128, capacity=4096,
        dtype=jnp.bfloat16, p_factor=2)
    found = pallas_passes.check_smem_footprint(big, "seeded")
    assert any(f.code == "smem-budget" and f.severity == Severity.ERROR
               for f in found), found
    # the resident layout keeps maps in VMEM: nothing for this pass
    res = fused_moe_pipeline_kernel_spec(
        64, 2048, 384, 128, 64 * 16 + 128, capacity=64,
        dtype=jnp.bfloat16, p_factor=2, streamed=False)
    assert res.smem_bytes() == 0
    assert pallas_passes.check_smem_footprint(res, "resident") == []


def test_stacked_launch_spec_reads_one_layer_in_place():
    """The layer scans' launch on layer-stacked weights: the layer index
    is one more SMEM control entry, each weight block squeezes the layer
    axis, and the grid, VMEM working set and every Pallas pass's findings
    are those of the launch on one layer's weights."""
    args = (256, 2048, 384, 128, 256 * 16 + 128)
    kw = dict(capacity=64, dtype=jnp.bfloat16, p_factor=2)
    flat = fused_moe_pipeline_kernel_spec(*args, **kw)
    stacked = fused_moe_pipeline_kernel_spec(*args, n_layers=48, **kw)
    one = {b.name: b for b in flat.blocks}
    by = {b.name: b for b in stacked.blocks}
    assert by["layer"].space == "smem" and by["layer"].control
    for w in ("w1", "w3", "w2"):
        assert by[w].shape == (1,) + one[w].shape
    assert stacked.grid == flat.grid
    assert stacked.vmem_bytes() == flat.vmem_bytes()
    assert stacked.smem_bytes() == flat.smem_bytes() + 4
    for check in (pallas_passes.check_vmem_footprint,
                  pallas_passes.check_smem_footprint,
                  pallas_passes.check_dma_streaming,
                  pallas_passes.check_mxu_alignment,
                  pallas_passes.check_grid_coverage):
        assert check(stacked, "e") == check(flat, "e")


def test_dma_pass_requires_staged_double_buffering():
    spec = fused_moe_pipeline_kernel_spec(
        256, 2048, 384, 128, 256 * 16 + 128, capacity=64,
        dtype=jnp.bfloat16, p_factor=2)
    assert pallas_passes.check_dma_streaming(spec, "ok") == []
    tampered = dataclasses.replace(spec, blocks=tuple(
        dataclasses.replace(b, dma_buffers=1) if b.name == "x" else b
        for b in spec.blocks))
    found = pallas_passes.check_dma_streaming(tampered, "seeded")
    assert any(f.code == "single-buffered-input" for f in found), found
    dead = dataclasses.replace(spec, blocks=tuple(
        dataclasses.replace(b, dma_buffers=0) if b.name == "out" else b
        for b in spec.blocks))
    found = pallas_passes.check_dma_streaming(dead, "seeded")
    assert any(f.code == "any-unreachable" and
               f.severity == Severity.ERROR for f in found), found


def test_mxu_pass_catches_misaligned_block():
    spec = grouped_swiglu_kernel_spec(4, 256, 256, 512, block_f=100)
    found = pallas_passes.check_mxu_alignment(spec, "seeded")
    assert any(f.code == "lane-misaligned" and
               f.severity == Severity.ERROR for f in found), found


def test_mxu_pass_full_axis_block_is_info_not_error():
    """olmoe-lite reduced: f/P = 64 < 128 lanes — the block spans the full
    axis, so the hardware pads it; must NOT be a CI-failing ERROR."""
    spec = grouped_swiglu_kernel_spec(8, 64, 256, 64, p_factor=1)
    found = pallas_passes.check_mxu_alignment(spec, "reduced")
    assert all(f.severity == Severity.INFO for f in found), found


def test_grid_pass_clean_on_real_specs_and_catches_tamper():
    spec = grouped_swiglu_kernel_spec(8, 200, 256, 96, p_factor=2)
    assert pallas_passes.check_grid_coverage(spec, "ok") == []
    bad = dataclasses.replace(spec, grid=(8, 1, spec.grid[2]))
    found = pallas_passes.check_grid_coverage(bad, "seeded")
    assert any(f.code == "grid-mismatch" for f in found), found
    worse = dataclasses.replace(
        spec, meta={**spec.meta, "n_minor_start": 10_000})
    assert any(f.code == "minor-boundary"
               for f in pallas_passes.check_grid_coverage(worse, "s"))


def test_kernel_specs_drive_the_launch():
    """The ragged-f geometry the launch uses comes FROM the spec: resolved
    meta must reproduce the padding/grid the kernel tests already pin."""
    spec = grouped_swiglu_kernel_spec(4, 100, 64, 96, block_c=128,
                                      block_f=128)
    m = spec.meta
    assert (m["block_c"], m["block_f"]) == (100, 96)   # clamped to dims
    assert m["pad_c"] == 0 and m["pad_f"] == 0
    assert spec.grid == (4, 1, 1)
    assert m["n_minor_start"] == 48                    # f//2 for even f
    # residency model: double-buffered streamed vmem blocks, single-counted
    # residents/scratch, SMEM maps and ANY-space arrays off the VMEM books
    fused = fused_moe_pipeline_kernel_spec(8, 16, 16, 2, 40, capacity=8)
    vmem = [b for b in fused.blocks if b.space == "vmem"]
    streamed = sum(2 * b.nbytes for b in vmem
                   if b.streamed and b.kind != "scratch")
    resident = sum(b.nbytes for b in vmem
                   if not b.streamed or b.kind == "scratch")
    assert fused.vmem_bytes() == streamed + resident
    assert fused.smem_bytes() == sum(b.nbytes for b in fused.blocks
                                     if b.space == "smem") > 0
    anys = {b.name: b for b in fused.blocks_of_space("any")}
    assert anys["x"].dma_buffers == 2 and anys["out"].dma_buffers == 1
    # the spec's staging scratch is what the kernel actually allocates:
    # 2x (block_c, d) gather tiles + accumulator + RMW stage
    names = {b.name for b in fused.blocks if b.kind == "scratch"}
    assert names == {"x_tiles", "acc_scratch", "out_stage"}


# ---------------------------------------------------------------------------
# bench schemas
# ---------------------------------------------------------------------------

def test_bench_schema_accepts_checked_in_files():
    assert bench_schema.check_bench_files(REPO) == []


def test_bench_schema_rejects_malformed(tmp_path):
    doc = json.loads((REPO / "BENCH_dispatch.json").read_text())
    assert bench_schema.validate_dispatch_bench(doc) == []
    del doc["rows"][0]["sort_us"]
    doc["smoke"] = "yes"
    errs = bench_schema.validate_dispatch_bench(doc)
    assert any("sort_us" in e for e in errs)
    assert any("smoke" in e for e in errs)
    (tmp_path / "BENCH_dispatch.json").write_text(json.dumps(doc))
    found = bench_schema.check_bench_files(tmp_path)
    assert all(f.severity == Severity.ERROR for f in found) and found


def test_bench_schema_rejects_malformed_pipeline_append():
    doc = json.loads((REPO / "BENCH_moe_pipeline.json").read_text())
    assert bench_schema.validate_pipeline_bench(doc) == []
    doc["runs"].append({"timestamp": "t", "host": {"backend": "cpu",
                                                   "devices": 1},
                        "smoke": False,
                        "rows": [{"T": 1}]})
    errs = bench_schema.validate_pipeline_bench(doc)
    assert any("buffer_us" in e for e in errs)


# ---------------------------------------------------------------------------
# baseline / runner / CLI
# ---------------------------------------------------------------------------

def test_baseline_suppression_globs(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"suppressions": [
        {"fingerprint": "pallas-vmem:*:kernel/fused_pipeline/*",
         "reason": "known"}], "hbm_bytes": {}}))
    b = Baseline.load(p)
    hit = Finding("pallas-vmem", "vmem-budget", Severity.ERROR,
                  "kernel/fused_pipeline/prod_prefill", "m")
    miss = Finding("pallas-vmem", "vmem-budget", Severity.ERROR,
                   "kernel/grouped_swiglu/prod", "m")
    assert b.suppression_for(hit) == "known"
    assert b.suppression_for(miss) is None


def test_runner_fast_matrix_clean_as_landed():
    """The acceptance bar, in-process flavor: jaxpr + spec families over
    the whole matrix (HLO compiles and engine traces run in the CI job's
    `python -m repro.lint --ci`)."""
    rep = run_lint(entries=build_entries(include_hlo=False,
                                         include_engine=False),
                   repo_root=REPO, baseline_path=REPO /
                   "lint_baseline.json")
    assert rep.exit_code == 0, rep.render(verbose=True)
    assert len(rep.entries_run) >= 10
    # the streamed rewrite removed the prod_prefill VMEM suppression — the
    # matrix must be clean with an EMPTY suppression list
    assert not rep.suppressed, [f.fingerprint for f in rep.suppressed]


def test_runner_survives_broken_entry():
    from repro.lint.registry import LintEntry

    def boom():
        raise RuntimeError("tracing exploded")

    rep = run_lint(entries=[LintEntry("broken/one", {}, boom)],
                   repo_root=REPO)
    assert rep.exit_code == 1
    assert any(f.code == "trace-error" for f in rep.findings)


def test_cli_subset_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--entries", "kernel/*",
         "--entries", "calib/*"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
