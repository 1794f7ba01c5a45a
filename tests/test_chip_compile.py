"""The chip's own compiler, without the chip.

`jax.experimental.topologies` describes a `v5e:2x2` device that is not
attached; compiling for it runs the real Mosaic and XLA:TPU compilers and
raises what the chip would raise (block alignment against the real tiling,
VMEM budgets) — what interpret mode and tests/test_tpu_lowering.py cannot
see. Nothing executes. One case per kernel that `chip_smoke.py`'s `train`
(GPT-2 124M) and `serve` (Llama at 8B widths) phases dispatch, at those
widths. A compile that passes here is a compile, never a chip run.

The topology is described inside a module-scoped fixture: only the worker
that runs this file loads the TPU library, and every worker collects the
same tests.
"""

from __future__ import annotations

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.kernels import _common as kern

# GPT-2 124M: batch 4 x 1024, 12 heads x 64, hidden 768, vocab 50304
GPT = dict(b=4, s=1024, h=12, kv=12, d=64, hidden=768, vocab=50304)
# Llama-3-8B: 32 heads / 8 kv heads x 128, hidden 4096, mlp 14336
LLAMA = dict(b=1, s=2048, h=32, kv=8, d=128, hidden=4096, mlp=14336,
             vocab=128256)


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def compile_for(one_chip, fn, *shapes):
    """Compile `fn` for the described chip from (shape, dtype) pairs and
    return the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with kern.x64_off():
        txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt, "no Mosaic kernel in the compiled HLO"
    return txt


@pytest.mark.parametrize("cfg", [GPT, LLAMA], ids=["gpt2_124m", "llama3_8b"])
def test_flash_attention_fwd_bwd(one_chip, cfg):
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    b, s, d = cfg["b"], cfg["s"], cfg["d"]
    q = ((b, s, cfg["h"], d), jnp.bfloat16)
    kv = ((b, s, cfg["kv"], d), jnp.bfloat16)
    compile_for(one_chip,
                functools.partial(fap.flash_attention_forward, causal=True),
                q, kv, kv)

    def fwd_bwd(q, k, v):
        out, lse = fap.flash_attention_forward_lse(q, k, v, causal=True)
        return fap.flash_attention_backward(q, k, v, out, lse,
                                            jnp.ones_like(out), causal=True)

    compile_for(one_chip, fwd_bwd, q, kv, kv)


@pytest.mark.parametrize("hidden", [GPT["hidden"], LLAMA["hidden"]])
def test_rms_norm_fused_fwd_bwd(one_chip, hidden):
    from paddle_tpu.ops.kernels import rms_norm_pallas as rn
    x = ((4, 1024, hidden), jnp.bfloat16)
    w = ((hidden,), jnp.bfloat16)
    fn = functools.partial(rn.rms_norm_fused, eps=1e-5, interpret=False)
    compile_for(one_chip, lambda a, b, r: fn(a, b, r), x, w, x)
    compile_for(one_chip, lambda a, b, r: jax.grad(
        lambda *t: jnp.sum(fn(*t)[0].astype(jnp.float32)),
        argnums=(0, 1, 2))(a, b, r), x, w, x)


@pytest.mark.parametrize("act,norm,hidden,width", [
    (None, "layer", GPT["hidden"], GPT["hidden"]),       # GPT attn junction
    ("gelu", "layer", GPT["hidden"], GPT["hidden"]),     # GPT mlp junction
    (None, "rms", LLAMA["hidden"], LLAMA["hidden"]),     # Llama junctions
], ids=["gpt_attn", "gpt_mlp_gelu", "llama_rms"])
def test_block_epilogue_fwd_bwd(one_chip, act, norm, hidden, width):
    from paddle_tpu.ops.kernels import block_fused_pallas as bf
    x = ((4, 1024, width), jnp.bfloat16)
    res = ((4, 1024, hidden), jnp.bfloat16)
    w = ((hidden,), jnp.float32)
    bias = norm == "layer"

    def fwd(x, res, w):
        b = jnp.zeros((hidden,), jnp.float32) if bias else None
        return bf.fused_epilogue(x, res, w, b, None, 0.0, 1e-5, act, norm,
                                 None, False)

    compile_for(one_chip, lambda *a: fwd(*a)[0], x, res, w)

    def fwd_bwd(x, res, w):
        def f(*t):
            y, h = fwd(*t)
            return jnp.sum(y.astype(jnp.float32)) + \
                jnp.sum(h.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(x, res, w)

    compile_for(one_chip, fwd_bwd, x, res, w)


@pytest.mark.parametrize("batch", [8, 1], ids=["decode_b8", "prefill_b1x16"])
def test_block_decode_epilogue(one_chip, batch):
    """The serving junction at hidden 4096: the decode step's [8, 1, H] and
    a 16-token prefill bucket's [1, 16, H]."""
    from paddle_tpu.ops.kernels import block_fused_pallas as bf
    shape = (8, 1, LLAMA["hidden"]) if batch == 8 else \
        (1, 16, LLAMA["hidden"])
    x = (shape, jnp.bfloat16)
    txt = compile_for(
        one_chip,
        lambda a, r, w: bf.decode_epilogue(a, r, w, 1e-5, False),
        x, x, ((LLAMA["hidden"],), jnp.bfloat16))
    assert "block_decode_epilogue" in txt


def test_swiglu_fwd_bwd(one_chip):
    from paddle_tpu.ops.kernels import swiglu_pallas as sg
    g = ((2048, LLAMA["mlp"]), jnp.bfloat16)
    compile_for(one_chip, lambda a, b: sg.swiglu_fused(a, b, False), g, g)
    compile_for(one_chip, lambda a, b: jax.grad(lambda t: jnp.sum(
        sg.swiglu_fused(t[0], t[1], False).astype(jnp.float32)))((a, b)),
        g, g)


@pytest.mark.parametrize("shape", [(GPT["vocab"], GPT["hidden"]),
                                   (GPT["hidden"], 4 * GPT["hidden"]),
                                   (4 * GPT["hidden"], GPT["hidden"])],
                         ids=str)
def test_adamw_update_is_one_loop_in_the_parameters_layout(one_chip, shape):
    """AdamW over a GPT-2 matrix as the optimizer feeds it (float32 master
    and moments, bfloat16 gradient and parameter, each in the parameter's
    own shape): the chip's compiler makes one fusion of it, and no copy,
    transpose or reshape of the tensor (PR 32: eight such copies a
    parameter were 17 % of GPT-2 medium's step)."""
    import re
    from paddle_tpu.optimizer.optimizers import _adam_update
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (shape, jnp.float32), (shape, jnp.bfloat16), (shape, jnp.float32),
        (shape, jnp.float32), ((), jnp.float32), ((), jnp.float32))]
    txt = jax.jit(lambda w, g, m, v, lr, t: _adam_update(
        w, g, m, v, None, lr, t, beta1=0.9, beta2=0.999, eps=1e-8,
        decay=0.1, out_dtype=jnp.bfloat16)).lower(*args).compile().as_text()
    dims = "[" + ",".join(map(str, shape)) + "]"
    # the entry computation's instructions whose result has the tensor's
    # shape: `%name = type[dims]{layout} opcode(operands)`
    ops = []
    for line in txt[txt.index("ENTRY"):].splitlines():
        made = re.match(r"\s+(?:ROOT )?%\S+ = (.*?) ([a-z][\w-]*)\(", line)
        if made and dims in made.group(1):
            ops.append(made.group(2))
    moved = set(ops) - {"parameter", "fusion", "get-tuple-element", "tuple"}
    assert ops.count("fusion") == 1 and not moved, ops


@pytest.mark.parametrize("n,vocab", [(4 * 1024, GPT["vocab"]),
                                     (2048, LLAMA["vocab"])],
                         ids=["gpt2_50304", "llama_128256"])
def test_sharded_ce_fwd_bwd(one_chip, n, vocab):
    from paddle_tpu.ops.kernels import ce_pallas as cp
    lg = ((n, vocab), jnp.float32)
    lb = ((n,), jnp.int32)
    compile_for(one_chip, lambda a, l: cp.c_softmax_with_cross_entropy(
        a, l, 0, None, False), lg, lb)
    compile_for(one_chip, lambda a, l: jax.grad(lambda t: jnp.sum(
        cp.c_softmax_with_cross_entropy(t, l, 0, None, False)))(a), lg, lb)


@pytest.mark.parametrize("t", [2048, 8192])
def test_mmha_decode(one_chip, t):
    """Decode attention at 32/8 heads x 128 over the engine's gathered
    [B, Hkv, T, D] view, per-row positions."""
    from paddle_tpu.ops.kernels import mmha_pallas
    b = 8
    q = ((b, 1, LLAMA["h"], LLAMA["d"]), jnp.bfloat16)
    kv = ((b, LLAMA["kv"], t, LLAMA["d"]), jnp.bfloat16)
    assert mmha_pallas.use_kernel(q[0], kv[0], jnp.bfloat16) or \
        not kern.available()
    compile_for(one_chip, lambda a, k, v, p: mmha_pallas.mmha_decode(
        a, k, v, p), q, kv, kv, ((b,), jnp.int32))


def test_paged_mmha_decode(one_chip):
    """The serving cells' decode attention: batch 32, 32/8 heads x 128,
    the whole bf16 pool of 16 layers x 2049 pages of 16 left in HBM,
    tables 256 wide. The pool must reach the kernel as it is: a copy of
    it in front of the call would cost more than the kernel saves."""
    from paddle_tpu.ops.kernels import mmha_pallas
    b, pages = 32, 256
    q = ((b, 1, LLAMA["h"], LLAMA["d"]), jnp.bfloat16)
    pool = ((16, 2049, LLAMA["kv"], 16, LLAMA["d"]), jnp.bfloat16)
    txt = compile_for(
        one_chip, lambda a, k, v, l, t, p: mmha_pallas.paged_mmha_decode(
            a, k, v, l, t, p), q, pool, pool, ((), jnp.int32),
        ((b, pages), jnp.int32), ((b,), jnp.int32))
    # the trace's name for the op: the benchmark finds the kernel by it
    assert "%paged_mmha_decode" in txt
    assert not [ln for ln in txt.splitlines()
                if " copy(" in ln and "2049" in ln.split(" copy(")[0]]


def test_rope(one_chip):
    from paddle_tpu.ops.kernels import rope_pallas as rp
    x = ((1, 2048, LLAMA["h"], LLAMA["d"]), jnp.bfloat16)
    cs = ((2048, LLAMA["d"]), jnp.float32)
    compile_for(one_chip, lambda a, c, s: rp.rope_apply(a, c, s, False),
                x, cs, cs)


def test_wo_int4_matmul(one_chip):
    """Weight-only int4: nibbles unpacked with int32 shifts (the chip has
    no int8 vector shifts). A K whose widened block cannot fit VMEM is
    refused at trace time, never handed to the compiler."""
    from paddle_tpu.ops.kernels import wo_matmul_pallas as wm
    fn = lambda x, w, s: wm.wo_int4_matmul(x, w, s)  # noqa: E731
    compile_for(one_chip, fn, ((8, 1024), jnp.bfloat16),
                ((1024, 2048), jnp.int8), ((4096,), jnp.float32))
    with pytest.raises(ValueError, match="cannot fit VMEM"):
        compile_for(one_chip, fn, ((8, 4096), jnp.bfloat16),
                    ((4096, 2048), jnp.int8), ((4096,), jnp.float32))


# Trinity-Mini (afmoe): 32 heads / 4 kv heads x 128, hidden 2048, 128
# experts of width 1024, window 2048; prompts to 16384
TRINITY = dict(h=32, kv=4, d=128, hidden=2048, expert=1024, experts=128,
               window=2048)


@pytest.mark.parametrize("s,window", [(16384, 2048), (16384, None),
                                      (256, 2048)],
                         ids=["window_16k", "global_16k", "window_256"])
def test_flash_attention_banded(one_chip, s, window):
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    c = TRINITY
    q = ((1, s, c["h"], c["d"]), jnp.bfloat16)
    kv = ((1, s, c["kv"], c["d"]), jnp.bfloat16)
    compile_for(one_chip, functools.partial(
        fap.flash_attention_forward_banded, window=window), q, kv, kv)


@pytest.mark.parametrize("rows,tile", [(2560, 16), (65536, 256)],
                         ids=["decode_64x8", "prefill_4096x8"])
def test_moe_grouped_kernels(one_chip, rows, tile):
    from paddle_tpu.ops.kernels import moe_gemm_pallas as mg
    c = TRINITY
    e, h, m = c["experts"], c["hidden"], c["expert"]
    te, used = ((rows // tile,), jnp.int32), ((), jnp.int32)
    compile_for(one_chip, functools.partial(mg.moe_grouped_swiglu, tile=tile),
                ((rows, h), jnp.bfloat16), ((e, h, m), jnp.bfloat16),
                ((e, h, m), jnp.bfloat16), te, used)
    compile_for(one_chip, functools.partial(mg.moe_grouped_matmul, tile=tile),
                ((rows, m), jnp.bfloat16), ((e, m, h), jnp.bfloat16), te,
                used)


def test_paged_mmha_decode_with_lower_bound(one_chip):
    from paddle_tpu.ops.kernels import mmha_pallas as mp
    c = TRINITY
    b, pages, ps, max_pages = 64, 8257, 16, 1088
    pool = ((3, pages, c["kv"], ps, c["d"]), jnp.bfloat16)
    compile_for(one_chip, mp.paged_mmha_decode,
                ((b, 1, c["h"], c["d"]), jnp.bfloat16), pool, pool,
                ((), jnp.int32), ((b, max_pages), jnp.int32),
                ((b,), jnp.int32), ((b,), jnp.int32))
