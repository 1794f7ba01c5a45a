"""Kernel analyzer (PK tier): one positive + one negative fixture per
rule, self-application over ops/kernels/ (clean modulo the justified
allowlist), the planted demo module tripping every ERROR rule, and
resource-sheet hand-checks against the in-file VMEM budgets of
mmha_pallas and block_fused_pallas."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.analysis.cli import apply_allowlist, load_allowlist
from paddle_tpu.analysis.diagnostics import ERROR, WARNING
from paddle_tpu.analysis.kernels import (ALLOWLIST_NAME, analyze_paths,
                                         collect, kernel_cost)
from paddle_tpu.analysis.kernels.model import extract_callable
from paddle_tpu.analysis.kernels.resources import resource_sheet
from paddle_tpu.analysis.kernels.rules import check_model, check_source
from paddle_tpu.cost_model import chip_vmem_bytes

S = jax.ShapeDtypeStruct
F32 = jnp.float32


def _rules(fn, *args, budget=None, **kwargs):
    """Rule ids fired by the single pallas_call inside `fn(*args)`."""
    models = extract_callable(fn, args, kwargs, label="fixture",
                              file="<fixture>")
    assert len(models) == 1, "fixture must contain exactly one pallas_call"
    m = models[0]
    sheet = resource_sheet(m, budget or chip_vmem_bytes())
    return {f.rule_id for f in check_model(m, sheet)}, m, sheet


def _copy_call(shape, block, in_map, out_map, grid, body=None,
               out_shape=None, out_block=None):
    """Minimal one-in/one-out pallas_call fixture builder."""
    def fn(x):
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            body or k, grid=grid,
            in_specs=[pl.BlockSpec(block, in_map)],
            out_specs=pl.BlockSpec(out_block or block, out_map),
            out_shape=S(out_shape or shape, F32))(x)
    return fn, S(shape, F32)


# ---------------------------------------------------------------------------
# PK200 — VMEM residency
# ---------------------------------------------------------------------------

def test_pk200_overflowing_block_flagged():
    # two 16 MiB f32 blocks resident per step >> the 16 MiB preset
    ident = lambda i: (0, 0)
    fn, x = _copy_call((4096, 1024), (4096, 1024), ident, ident, (1,))
    rules, _, sheet = _rules(fn, x)
    assert "PK200" in rules
    assert not sheet.fits_vmem
    assert sheet.block_bytes == 2 * 4096 * 1024 * 4


def test_pk200_small_block_clean():
    ident = lambda i: (0, 0)
    fn, x = _copy_call((128, 128), (128, 128), ident, ident, (1,))
    rules, _, sheet = _rules(fn, x)
    assert "PK200" not in rules
    assert sheet.fits_vmem


# ---------------------------------------------------------------------------
# PK201/PK202/PK203 — abstract evaluation over the grid
# ---------------------------------------------------------------------------

def test_pk201_nonconsecutive_output_revisit_flagged():
    # out block (j, 0) over grid (i, j): block 0 written at steps
    # (0,0) and (1,0) with (0,1) in between — a lost-write race
    fn, x = _copy_call((2, 128), (1, 128),
                       lambda i, j: (i, 0), lambda i, j: (j, 0), (2, 2))
    rules, _, _ = _rules(fn, x)
    assert "PK201" in rules
    assert rules.isdisjoint({"PK202", "PK203"})


def test_pk201_consecutive_revisit_clean():
    # same revisit pattern but consecutive (accumulation idiom) — fine
    fn, x = _copy_call((2, 128), (1, 128),
                       lambda i, j: (i, 0), lambda i, j: (i, 0), (2, 2))
    rules, _, _ = _rules(fn, x)
    assert "PK201" not in rules


def test_pk202_uncovered_output_blocks_flagged():
    # 4 output blocks, grid only writes the first 2
    fn, x = _copy_call((2, 128), (1, 128),
                       lambda i: (i, 0), lambda i: (i, 0), (2,),
                       out_shape=(4, 128))
    rules, _, _ = _rules(fn, x)
    assert "PK202" in rules


def test_pk203_out_of_bounds_index_map_flagged():
    # input map i -> i+1 walks off the end of a 2-block ref
    fn, x = _copy_call((128, 128), (64, 128),
                       lambda i: (i + 1, 0), lambda i: (i, 0), (2,))
    rules, _, _ = _rules(fn, x)
    assert "PK203" in rules


def test_pk20x_identity_grid_clean():
    fn, x = _copy_call((128, 128), (64, 128),
                       lambda i: (i, 0), lambda i: (i, 0), (2,))
    rules, _, _ = _rules(fn, x)
    assert rules.isdisjoint({"PK201", "PK202", "PK203"})


# ---------------------------------------------------------------------------
# PK204 — unmasked tails
# ---------------------------------------------------------------------------

def test_pk204_unmasked_tail_flagged():
    # 100 rows % 64-row block leaves a 36-row tail; body never masks
    fn, x = _copy_call((100, 128), (64, 128),
                       lambda i: (i, 0), lambda i: (i, 0), (2,))
    rules, _, _ = _rules(fn, x)
    assert "PK204" in rules


def test_pk204_masked_tail_clean():
    def fn(x):
        def k(x_ref, o_ref):
            rows = jax.lax.broadcasted_iota(jnp.int32, (64, 128), 0)
            o_ref[...] = jnp.where(rows < 100, x_ref[...], 0.0)
        return pl.pallas_call(
            k, grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            out_shape=S((100, 128), F32))(x)
    rules, _, _ = _rules(fn, S((100, 128), F32))
    assert "PK204" not in rules


# ---------------------------------------------------------------------------
# PK206 — AST plane (pallas_call outside x64_off)
# ---------------------------------------------------------------------------

def test_pk206_pallas_call_outside_x64_off_flagged():
    src = (
        "def f(x):\n"
        "    return pl.pallas_call(_k, out_shape=o)(x)\n")
    fs = check_source(src, "fix.py")
    assert any(f.rule_id == "PK206" and "x64_off" in f.message for f in fs)


def test_pk206_pallas_call_under_x64_off_clean():
    src = (
        "def f(x):\n"
        "    with x64_off():\n"
        "        return pl.pallas_call(_k, out_shape=o)(x)\n"
        "@jit_x64_off\n"
        "def g(x):\n"
        "    return pl.pallas_call(_k, out_shape=o)(x)\n")
    assert check_source(src, "fix.py") == []


# ---------------------------------------------------------------------------
# PK207 — low-precision accumulation
# ---------------------------------------------------------------------------

def _dot_fixture(preferred):
    def fn(a, b):
        def k(a_ref, b_ref, o_ref):
            kw = ({"preferred_element_type": jnp.float32}
                  if preferred else {})
            acc = jax.lax.dot_general(
                a_ref[...], b_ref[...], (((1,), (0,)), ((), ())), **kw)
            o_ref[...] = acc.astype(jnp.bfloat16)
        ident = lambda i: (0, 0)
        return pl.pallas_call(
            k, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), ident),
                      pl.BlockSpec((128, 128), ident)],
            out_specs=pl.BlockSpec((8, 128), ident),
            out_shape=S((8, 128), jnp.bfloat16))(a, b)
    return fn, S((8, 128), jnp.bfloat16), S((128, 128), jnp.bfloat16)


def test_pk207_bf16_accumulation_flagged():
    fn, a, b = _dot_fixture(preferred=False)
    rules, _, _ = _rules(fn, a, b)
    assert "PK207" in rules


def test_pk207_f32_accumulation_clean():
    fn, a, b = _dot_fixture(preferred=True)
    rules, _, _ = _rules(fn, a, b)
    assert "PK207" not in rules


# ---------------------------------------------------------------------------
# PK208 — scalar-prefetch misuse
# ---------------------------------------------------------------------------

def _prefetch_fixture(dtype, use_in_map, use_in_body=False):
    def fn(p, x):
        def k(p_ref, x_ref, o_ref):
            if use_in_body:
                o_ref[...] = x_ref[...] + p_ref[0]
            else:
                o_ref[...] = x_ref[...]
        in_map = ((lambda i, pr: (pr[0], 0)) if use_in_map
                  else (lambda i, pr: (0, 0)))
        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), in_map)],
            out_specs=pl.BlockSpec((8, 128), lambda i, pr: (0, 0)))
        return pl.pallas_call(k, grid_spec=gs,
                              out_shape=S((8, 128), F32))(p, x)
    return fn, S((1,), dtype), S((8, 128), F32)


def test_pk208_unused_prefetch_flagged():
    fn, p, x = _prefetch_fixture(jnp.int32, use_in_map=False)
    rules, m, _ = _rules(fn, p, x)
    assert "PK208" in rules
    assert m.num_scalar_prefetch == 1


def test_pk208_float_prefetch_flagged():
    # index maps reject float outputs at trace time, so the misuse shape
    # is a float prefetch consumed in the body: it prefetches nothing's
    # blocking and must be integer
    fn, p, x = _prefetch_fixture(jnp.float32, use_in_map=False,
                                 use_in_body=True)
    rules, _, _ = _rules(fn, p, x)
    assert "PK208" in rules


def test_pk208_integer_prefetch_steering_map_clean():
    fn, p, x = _prefetch_fixture(jnp.int32, use_in_map=True)
    rules, _, _ = _rules(fn, p, x)
    assert "PK208" not in rules


# ---------------------------------------------------------------------------
# PK209 — dead operands
# ---------------------------------------------------------------------------

def test_pk209_untouched_scratch_flagged():
    def fn(x):
        def k(x_ref, o_ref, acc_ref):
            o_ref[...] = x_ref[...]
        ident = lambda i: (0, 0)
        return pl.pallas_call(
            k, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), ident)],
            out_specs=pl.BlockSpec((8, 128), ident),
            out_shape=S((8, 128), F32),
            scratch_shapes=[pltpu.VMEM((8, 128), F32)])(x)
    rules, m, sheet = _rules(fn, S((8, 128), F32))
    assert "PK209" in rules
    assert sheet.scratch_bytes == 8 * 128 * 4


def test_pk209_unread_input_block_flagged():
    def fn(a, b):
        def k(a_ref, b_ref, o_ref):
            o_ref[...] = a_ref[...]
        ident = lambda i: (0, 0)
        return pl.pallas_call(
            k, grid=(1,),
            in_specs=[pl.BlockSpec((8, 128), ident),
                      pl.BlockSpec((8, 128), ident)],
            out_specs=pl.BlockSpec((8, 128), ident),
            out_shape=S((8, 128), F32))(a, b)
    rules, _, _ = _rules(fn, S((8, 128), F32), S((8, 128), F32))
    assert "PK209" in rules


def test_clean_kernel_has_no_findings():
    ident = lambda i: (0, 0)
    fn, x = _copy_call((8, 128), (8, 128), ident, ident, (1,))
    rules, _, _ = _rules(fn, x)
    assert rules == set()


# ---------------------------------------------------------------------------
# self-application and the planted demo
# ---------------------------------------------------------------------------

def test_self_application_clean_modulo_allowlist():
    findings, sheets = collect(
        [os.path.join(REPO, "paddle_tpu", "ops", "kernels")])
    entries = load_allowlist(os.path.join(REPO, ALLOWLIST_NAME))
    kept, waived = apply_allowlist(findings, entries)
    errors = [f for f in kept if f.severity == ERROR]
    assert errors == [], [f"{f.rule_id} {f.file}:{f.line}" for f in errors]
    # the allowlist documents real, justified findings — it must keep
    # matching something, or it has gone stale
    assert waived
    assert len(sheets) >= 30
    # no extraction-failure notes: every pk_examples() entry traces
    assert not any("failed" in f.message
                   for f in kept if f.rule_id == "PK209")


def test_demo_trips_every_error_rule():
    demo = os.path.join(REPO, "paddle_tpu", "analysis", "kernels", "demo.py")
    fs = analyze_paths([demo])
    errs = {f.rule_id for f in fs if f.severity == ERROR}
    assert {"PK200", "PK201", "PK202", "PK203", "PK206"} <= errs


# ---------------------------------------------------------------------------
# resource-sheet hand-checks vs the in-file budgets
# ---------------------------------------------------------------------------

def test_mmha_sheet_matches_infile_budget():
    cost = kernel_cost("paddle_tpu.ops.kernels.mmha_pallas")
    sheet = next(s for s in cost["kernels"] if s["kernel"] == "_mmha_kernel")
    # pk_examples decode shape: q/o blocks (1,1,8,128) bf16, k/v blocks
    # (1,1,2048,128) bf16 — hand-computed residency
    kv = 2 * 2048 * 128 * 2
    assert sheet["block_bytes"] == kv + 2 * 8 * 128 * 2
    # the in-file dispatch gate budgets exactly the k+v residency
    # (use_kernel: 2*t*d*itemsize <= chip_vmem_bytes() // 2); the
    # analyzer's total adds q/o blocks + body intermediates — within 25%
    # of the gated quantity at decode shapes (q/o are tiny next to the
    # cache)
    assert kv <= chip_vmem_bytes() // 2
    assert kv <= sheet["vmem_bytes"] <= int(kv * 1.25)
    assert sheet["fits_vmem"]
    assert cost["vmem_budget"] == chip_vmem_bytes()


def test_block_fused_sheet_matches_infile_budget():
    cost = kernel_cost("paddle_tpu.ops.kernels.block_fused_pallas")
    sheet = next(s for s in cost["kernels"]
                 if s["label"] == "attn_epilogue_fwd")
    # 4 row blocks (128,1024) bf16 + the (1,1024) bf16 norm weight
    assert sheet["block_bytes"] == 4 * 128 * 1024 * 2 + 1024 * 2
    # _pick_rows sizes row blocks against chip_vmem_bytes()//4; the
    # analyzer's full residency (blocks + intermediates) must honor the
    # same in-file budget
    assert sheet["vmem_bytes"] <= chip_vmem_bytes() // 4
    assert sheet["fits_vmem"]


def test_kernel_cost_accepts_module_path_and_dotted_name():
    path = os.path.join(REPO, "paddle_tpu", "ops", "kernels",
                        "swiglu_pallas.py")
    by_path = kernel_cost(path)
    by_name = kernel_cost("paddle_tpu.ops.kernels.swiglu_pallas")
    assert by_path["kernels"] == by_name["kernels"]
    assert by_name["chip"] == by_path["chip"]


def test_bench_kernel_static_cross_check():
    import bench
    block = bench._kernel_static_block(None)
    assert "error" not in block, block.get("error")
    assert block["sheets"] and block["joined"]
    cc = block["graph_cross_check"]
    # documented tolerance: pallas re-reads broadcast blocks / pads
    # tails vs the graph tier's count-each-array-once — 2x either way
    assert cc["tolerance"] == [0.5, 2.0]
    assert cc["ok"], cc
    assert cc["sheet_hbm_bytes"] == cc["graph_io_bytes"]
