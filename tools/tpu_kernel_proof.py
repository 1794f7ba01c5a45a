"""Run every Pallas kernel family on the attached TPU and record the evidence.

For each kernel family it runs the real `pallas_call` on the chip, compares
numerics against the XLA composite the kernel replaces (fwd AND grads where
the family has a vjp), times both, and writes
`chiprun_out/TPU_KERNEL_PROOF.json`. One process; without a TPU it fails
(`PROOF_INTERPRET=1` dry-runs the harness in interpret mode on the CPU and
writes a file that is not evidence).

    python tools/tpu_kernel_proof.py

Each family records: ok, max_err (vs composite in f32), pallas_ms, xla_ms,
speedup, and the error string on failure — a failing family must show up as
`ok: false`, never vanish.
"""

import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT_DIR = os.path.join(REPO, "chiprun_out")
OUT = os.path.join(OUT_DIR, "TPU_KERNEL_PROOF.json")
OUT_DRY = os.path.join(OUT_DIR, "tpu_kernel_proof_interp.json")  # NOT evidence


def _timed(fn, *args, iters=10):
    import jax
    jf = jax.jit(fn)
    r = jf(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = jf(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e3, r


def _maxerr(a, b):
    """(max abs err, max |ref|) — the gate is RELATIVE: outputs/grads here
    are bf16 at magnitudes up to O(100), where one bf16 ulp is ~0.5, so an
    absolute gate would flag healthy kernels."""
    import jax.numpy as jnp
    fa = jnp.asarray(a, jnp.float32).ravel()
    fb = jnp.asarray(b, jnp.float32).ravel()
    return (float(jnp.max(jnp.abs(fa - fb))),
            float(jnp.max(jnp.abs(fb))))


def _grad_of(f, n_args):
    import jax
    import jax.numpy as jnp

    def loss(*args):
        out = f(*args)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(jnp.asarray(l, jnp.float32) ** 2) for l in leaves)
    return jax.grad(loss, argnums=tuple(range(n_args)))


def run_family(name, pallas_fn, ref_fn, args, n_grad_args=0, tol=5e-2):
    """Time + compare pallas vs composite on the same inputs. A family
    that raises is recorded `ok: false` with its error; the rest still
    run."""
    res = {"ok": False}

    def rel(pairs):
        return max(e / max(m, 1e-6) for e, m in pairs)

    def _body():
        p_ms, p_out = _timed(pallas_fn, *args)
        x_ms, x_out = _timed(ref_fn, *args)
        import jax
        errs = [_maxerr(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(p_out), jax.tree_util.tree_leaves(x_out))]
        res.update(fwd_pallas_ms=round(p_ms, 3), fwd_xla_ms=round(x_ms, 3),
                   fwd_speedup=round(x_ms / p_ms, 3),
                   fwd_max_err=round(max(e for e, _ in errs), 6),
                   fwd_rel_err=round(rel(errs), 6))
        if n_grad_args:
            gp_ms, gp = _timed(_grad_of(pallas_fn, n_grad_args), *args,
                               iters=5)
            gx_ms, gx = _timed(_grad_of(ref_fn, n_grad_args), *args, iters=5)
            gerrs = [_maxerr(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(gp),
                jax.tree_util.tree_leaves(gx))]
            res.update(bwd_pallas_ms=round(gp_ms, 3),
                       bwd_xla_ms=round(gx_ms, 3),
                       bwd_speedup=round(gx_ms / gp_ms, 3),
                       bwd_max_err=round(max(e for e, _ in gerrs), 6),
                       bwd_rel_err=round(rel(gerrs), 6))
        worst = max(res.get("fwd_rel_err", 0.0), res.get("bwd_rel_err", 0.0))
        res["ok"] = worst <= tol
        if not res["ok"]:
            res["error"] = f"rel err {worst} > tol {tol}"

    try:
        _body()
    except Exception:
        res["error"] = traceback.format_exc(limit=6)[:1500]
    return res


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    interp = os.environ.get("PROOF_INTERPRET") == "1"
    dev = jax.devices()[0]
    if not interp and dev.platform != "tpu":
        print(json.dumps({"error": f"no tpu: {dev.platform}"}))
        return 1
    os.makedirs(OUT_DIR, exist_ok=True)
    if interp:
        from paddle_tpu.ops.kernels import _common as kern
        kern.force_interpret(True)
    report = {"device": str(getattr(dev, "device_kind", dev.platform)),
              "jax": jax.__version__, "ts": time.time(), "families": {}}

    class _CheckpointDict(dict):
        """Persists the in-progress report after every family: a run
        that dies mid-harness keeps the families that already ran (a
        report without a "summary" key is a partial one)."""

        def __setitem__(self, k, v):
            super().__setitem__(k, v)
            try:
                path = OUT_DRY if interp else OUT
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(report, fh, indent=1)
                os.replace(tmp, path)
            except Exception:
                pass

    fam = report["families"] = _CheckpointDict()
    rng = np.random.default_rng(0)
    SEQ = 256 if interp else 1024
    ROWS = 256 if interp else 4096
    NADAM = 8 * 1024 + 13 if interp else 4096 * 1024 + 13
    TMAX = 256 if interp else 2048
    VOCAB = 2048 if interp else 50304

    # 1. flash attention (MHA + GQA), causal, bf16, Llama-bench shape
    from paddle_tpu.ops.kernels import flash_attention as fa
    q, k, v = (jnp.asarray(rng.standard_normal((2, SEQ, 16, 64)),
                           jnp.bfloat16) for _ in range(3))
    fam["flash_attention"] = run_family(
        "flash_attention",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        lambda q, k, v: fa._reference_attention(q, k, v, True),
        (q, k, v), n_grad_args=3, tol=2e-2)
    kg, vg = (jnp.asarray(rng.standard_normal((2, SEQ, 4, 64)),
                          jnp.bfloat16) for _ in range(2))
    fam["flash_attention_gqa"] = run_family(
        "flash_attention_gqa",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        lambda q, k, v: fa._reference_attention(q, k, v, True),
        (q, kg, vg), n_grad_args=3, tol=2e-2)

    # 2. fused rmsnorm + residual
    from paddle_tpu.ops.kernels import rms_norm_pallas as rn
    x = jnp.asarray(rng.standard_normal((4, 512, 1024)), jnp.bfloat16)
    resid = jnp.asarray(rng.standard_normal((4, 512, 1024)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(1024), jnp.float32)

    def rn_ref(x, w, r):
        h = (x + r).astype(jnp.float32)
        o = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
        return (o * w).astype(x.dtype), h.astype(x.dtype)
    fam["rms_norm_fused"] = run_family(
        "rms_norm_fused",
        lambda x, w, r: rn.rms_norm_fused(x, w, r, 1e-5, interp),
        rn_ref, (x, w, resid), n_grad_args=2, tol=5e-2)

    # 3. rope fwd/bwd
    from paddle_tpu.ops.kernels import rope_pallas as rp
    b, s, h, d = 2, 2 * SEQ, 16, 128
    xr = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    ang = np.outer(np.arange(s), 1.0 / (10000 ** (np.arange(0, d, 2) / d)))
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1),
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1),
                      jnp.float32)
    fam["rope"] = run_family(
        "rope",
        lambda a: rp.rope_apply(a, cos, sin, interp),
        lambda a: rp.rope_reference(a, cos, sin),
        (xr,), n_grad_args=1, tol=2e-2)

    # 5. MoE grouped-GEMM (zero-padded rows precondition)
    from paddle_tpu.ops.kernels import moe_gemm_pallas as mg
    e, c, hh, f = (4, 64, 256, 512) if interp else (16, 128, 1024, 1408)
    counts = jnp.asarray(rng.choice([0, 16, 64, 128], e), jnp.int32)
    maskc = jnp.arange(c)[None, :, None] < counts.reshape(-1, 1, 1)
    xg = jnp.where(maskc, jnp.asarray(
        rng.standard_normal((e, c, hh)), jnp.bfloat16), 0)
    wg = jnp.asarray(rng.standard_normal((e, hh, f)), jnp.bfloat16)
    fam["moe_grouped_gemm"] = run_family(
        "moe_grouped_gemm",
        lambda a, b_: mg.grouped_matmul(a, b_, counts, interp),
        lambda a, b_: mg.reference_grouped_matmul(a, b_, counts),
        (xg, wg), tol=5e-1)

    # 6. fused bias+dropout+residual+layernorm
    from paddle_tpu.ops.kernels import bias_dropout_ln_pallas as bd
    rows, hid = ROWS, 2048
    xb = jnp.asarray(rng.standard_normal((rows, hid)), jnp.bfloat16)
    rb = jnp.asarray(rng.standard_normal((rows, hid)), jnp.bfloat16)
    bias = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    gam = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    bet = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    mask2 = jnp.asarray(rng.random((rows, hid)) > 0.1, jnp.float32) / 0.9
    fam["bias_dropout_ln"] = run_family(
        "bias_dropout_ln",
        lambda x_, r_, g_: bd.bias_dropout_ln(
            x_, bias, r_, mask2, g_, bet, 1e-5, interp),
        lambda x_, r_, g_: bd.reference_bias_dropout_ln(
            x_, bias, r_, mask2, g_, bet, 1e-5),
        (xb, rb, gam), n_grad_args=3, tol=5e-2)

    # 7. fused (sharded-vocab) softmax cross-entropy
    from paddle_tpu.ops.kernels import ce_pallas as cp
    nrows, vocab = 2048, VOCAB
    lg = jnp.asarray(rng.standard_normal((nrows, vocab)), jnp.bfloat16)
    lb = jnp.asarray(rng.integers(0, vocab, (nrows,)), jnp.int32)
    fam["softmax_ce"] = run_family(
        "softmax_ce",
        lambda a: cp.c_softmax_with_cross_entropy(a, lb, 0, None, interp),
        lambda a: cp.reference_ce(a, lb),
        (lg,), n_grad_args=1, tol=2e-2)

    # 8. decode attention (mmha) over the [B, Hkv, T, D] KV cache layout
    from paddle_tpu.ops.kernels import mmha_pallas as mm
    bq, hq, hkv, dq, tmax = 8, 16, 4, 128, TMAX
    qd = jnp.asarray(rng.standard_normal((bq, 1, hq, dq)), jnp.bfloat16)
    kb = jnp.asarray(rng.standard_normal((bq, hkv, tmax, dq)), jnp.bfloat16)
    vb = jnp.asarray(rng.standard_normal((bq, hkv, tmax, dq)), jnp.bfloat16)
    pos = jnp.asarray(3 * tmax // 4, jnp.int32)
    fam["mmha_decode"] = run_family(
        "mmha_decode",
        lambda q_, k_, v_: mm.mmha_decode(q_, k_, v_, pos, interpret=interp),
        lambda q_, k_, v_: mm.reference_mmha(q_, k_, v_, pos),
        (qd, kb, vb), tol=2e-2)

    # 8b. the serving decode program's attention: the same math straight
    # from the paged pool [L, P, Hkv, ps, D] through page tables, against
    # the composite over the page-table gather
    from paddle_tpu.serving import kv_cache as kvc
    ps, n_tab = 16, tmax // 16
    n_pool = bq * n_tab + 1
    kpool = jnp.asarray(rng.standard_normal((2, n_pool, hkv, ps, dq)),
                        jnp.bfloat16)
    vpool = jnp.asarray(rng.standard_normal((2, n_pool, hkv, ps, dq)),
                        jnp.bfloat16)
    tabs = jnp.asarray(rng.permutation(np.arange(1, n_pool))
                       .reshape(bq, n_tab), jnp.int32)
    posv = jnp.asarray(rng.integers(0, tmax, (bq,)), jnp.int32)
    fam["paged_mmha_decode"] = run_family(
        "paged_mmha_decode",
        lambda q_, k_, v_: mm.paged_mmha_decode(
            q_, k_, v_, jnp.int32(1), tabs, posv, interpret=interp),
        lambda q_, k_, v_: kvc.paged_attention(
            q_, k_, v_, 1, tabs, posv, interpret=False),
        (qd, kpool, vpool), tol=2e-2)

    # 9. weight-only int8 matmul (decode GEMV shape)
    from paddle_tpu.ops.kernels import wo_matmul_pallas as wm
    kk, nn_ = (512, 1024) if interp else (4096, 11008)
    wq = jnp.asarray(rng.integers(-127, 127, (kk, nn_)), jnp.int8)
    sc = jnp.asarray(rng.random(nn_) * 0.01, jnp.float32)
    xw = jnp.asarray(rng.standard_normal((8, kk)), jnp.bfloat16)
    fam["wo_int8_matmul"] = run_family(
        "wo_int8_matmul",
        lambda a: wm.wo_int8_matmul(a, wq, sc, interpret=interp),
        lambda a: wm.reference_wo_int8_matmul(a, wq, sc),
        (xw,), tol=5e-2)

    # 9a'. grouped-scale int8 weight-only matmul (rescale in VMEM)
    scg = jnp.asarray(rng.random((kk // 128, nn_)) * 0.01, jnp.float32)
    fam["wo_int8_grouped_matmul"] = run_family(
        "wo_int8_grouped_matmul",
        lambda a: wm.wo_int8_matmul(a, wq, scg, interpret=interp),
        lambda a: wm.reference_wo_int8_matmul(a, wq, scg),
        (xw,), tol=5e-2)

    # 9b. int4 weight-only matmul (packed halves layout)
    wq4 = jnp.asarray(rng.integers(-127, 127, (kk, nn_ // 2)), jnp.int8)
    sc4 = jnp.asarray(rng.random(nn_) * 0.01, jnp.float32)
    fam["wo_int4_matmul"] = run_family(
        "wo_int4_matmul",
        lambda a: wm.wo_int4_matmul(a, wq4, sc4, interpret=interp),
        lambda a: wm.reference_wo_int4_matmul(a, wq4, sc4),
        (xw,), tol=5e-2)

    # 10. segment-masked flash attention (varlen packing)
    segs = jnp.asarray(
        np.repeat(np.arange(4), SEQ // 4)[None].repeat(2, 0), jnp.int32)
    fam["flash_attention_segments"] = run_family(
        "flash_attention_segments",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                           segment_ids=segs),
        lambda q, k, v: fa._reference_attention(q, k, v, True, segs),
        (q, k, v), n_grad_args=3, tol=2e-2)

    # 11. fused SwiGLU (packed + two-arg MLP gate glue)
    from paddle_tpu.ops.kernels import swiglu_pallas as sg
    gr = jnp.asarray(rng.standard_normal((ROWS, 2048)), jnp.bfloat16)
    ur = jnp.asarray(rng.standard_normal((ROWS, 2048)), jnp.bfloat16)
    fam["swiglu"] = run_family(
        "swiglu",
        lambda a, b_: sg.swiglu_fused(a, b_, interp),
        lambda a, b_: sg.reference_swiglu(a, b_),
        (gr, ur), n_grad_args=2, tol=5e-2)
    xpk = jnp.concatenate([gr, ur], axis=-1)
    fam["swiglu_packed"] = run_family(
        "swiglu_packed",
        lambda a: sg.swiglu_packed(a, interp),
        lambda a: sg.reference_swiglu(a),
        (xpk,), n_grad_args=1, tol=5e-2)

    # 11b. fused LAMB (two-pass trust-ratio update)
    from paddle_tpu.ops.kernels import lamb_pallas as lp
    wl = jnp.asarray(rng.standard_normal(NADAM), jnp.float32)
    gl = jnp.asarray(rng.standard_normal(NADAM), jnp.float32)
    ml = jnp.asarray(rng.standard_normal(NADAM) * 0.1, jnp.float32)
    vl = jnp.asarray(rng.random(NADAM) * 0.01, jnp.float32)
    fam["fused_lamb"] = run_family(
        "fused_lamb",
        lambda w_, g_, m_, v_: lp.lamb_update(
            w_, g_, m_, v_, 1e-3, 2.0, beta1=0.9, beta2=0.999, eps=1e-6,
            wd=0.01, out_dtype=jnp.bfloat16, interpret=interp)[:3],
        lambda w_, g_, m_, v_: lp.reference_lamb(
            w_, g_, m_, v_, 1e-3, 2.0, beta1=0.9, beta2=0.999, eps=1e-6,
            wd=0.01)[:3],
        (wl, gl, ml, vl), tol=5e-2)

    # 12. fused masked softmax (additive mask + in-kernel causal triangle)
    from paddle_tpu.ops.kernels import softmax_mask_pallas as sm
    bsm, hsm, sqm = (2, 4, SEQ // 2) if interp else (4, 16, 1024)
    xs = jnp.asarray(rng.standard_normal((bsm, hsm, sqm, sqm)), jnp.bfloat16)
    msk = jnp.asarray(
        np.where(rng.random((bsm, 1, sqm, sqm)) > 0.1, 0.0, -1e9),
        jnp.bfloat16)
    fam["softmax_mask"] = run_family(
        "softmax_mask",
        lambda a: sm.softmax_mask_fused(a, msk, interp),
        lambda a: sm.reference_softmax_mask(a, msk),
        (xs,), n_grad_args=1, tol=2e-2)
    fam["softmax_mask_tri"] = run_family(
        "softmax_mask_tri",
        lambda a: sm.softmax_mask_tri(a, interp),
        lambda a: sm.reference_softmax_mask(a),
        (xs,), n_grad_args=1, tol=2e-2)

    # 16. fused dropout + residual add (counter-hash mask, r5)
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak
    xd = jnp.asarray(rng.standard_normal((ROWS, 1024)), jnp.bfloat16)
    rd = jnp.asarray(rng.standard_normal((ROWS, 1024)), jnp.bfloat16)
    sd = jnp.int32(17)
    fam["dropout_add"] = run_family(
        "dropout_add",
        lambda a, r: dak.dropout_add(a, r, sd, 0.1, interp),
        lambda a, r: dak.reference_dropout_add(a, r, sd, 0.1),
        (xd, rd), n_grad_args=2, tol=2e-2)

    # 17. fused linear param-grad accumulate (r5)
    from paddle_tpu.ops.kernels import linear_grad_add_pallas as lga
    xga = jnp.asarray(rng.standard_normal((ROWS, 512)), jnp.bfloat16)
    dyga = jnp.asarray(rng.standard_normal((ROWS, 768)), jnp.bfloat16)
    accga = jnp.asarray(rng.standard_normal((512, 768)), jnp.float32)
    fam["linear_grad_acc"] = run_family(
        "linear_grad_acc",
        lambda a, b: lga.linear_grad_acc(a, b, accga, interp),
        lambda a, b: lga.reference_grad_acc(a, b, accga),
        (xga, dyga), tol=2e-2)

    # 18. A8W8 int8 matmul (in-kernel per-token quant, r5)
    from paddle_tpu.ops.kernels import a8w8_matmul_pallas as a8
    xa8 = jnp.asarray(rng.standard_normal((ROWS, 1024)), jnp.bfloat16)
    wa8 = jnp.asarray(rng.integers(-127, 128, (1024, 1024)), jnp.int8)
    wsa8 = jnp.asarray(rng.random(1024) * 0.02 + 0.01, jnp.float32)
    fam["a8w8_matmul"] = run_family(
        "a8w8_matmul",
        lambda a: a8.a8w8_matmul(a, wa8, wsa8, interpret=interp),
        lambda a: a8.reference_a8w8(a, wa8, wsa8),
        (xa8,), tol=5e-2)

    n_ok = sum(1 for v in fam.values() if v.get("ok"))
    report["summary"] = {"ok": n_ok, "total": len(fam),
                         "all_ok": n_ok == len(fam)}
    with open(OUT_DRY if interp else OUT, "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report["summary"]))
    for k, v in fam.items():
        print(k, "OK" if v.get("ok") else "FAIL",
              {kk: vv for kk, vv in v.items() if kk != "error"})
        if v.get("error"):
            print("  ", v["error"].splitlines()[-1][:200])
    return 0 if report["summary"]["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
