"""The four serving programs (decode, prefill, prefill chunk, speculative
verify) of `serving/model.py`, one layer loop under all of them, on models
the CPU holds: a 2-layer Llama (plain; with the fused junctions in interpret
mode; with `weight_only_int8` linears) and AFMoE at the size of
`perfbench/tests/data/configs/afmoe-tiny.json` (window and global layers,
routed experts, four norms a layer).

(a) each program's logits at every real position against the model's own
    dense forward over the whole sequence (no cache); an int8 engine against
    the same forward with the seven linears' weights put through int8 and
    back, which is what `weight_only_linear` multiplies with;
(b) what the engine refuses for a model that is not plain;
(c) no 64-bit value in a program's jaxpr outside a list kept here;
(d) the scopes the benchmark's device shares read, from the name stacks;
(e) the kernels' cost sheets: a roofline, nothing measured;
(f) the decode-layer kernel's option is gone, not ignored.

Both models compute in float32 here; program and dense forward differ in
the order of their sums (`TOL`).
"""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import llama_tiny
from paddle_tpu.ops.kernels import _common as kern
from paddle_tpu.serving import LLMEngine, ServingConfig
from paddle_tpu.serving.model import ServingModel

from perfbench.drivers import serve_afmoe as drv
from perfbench.harness import common

TOL = 5e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data", "configs",
                       "afmoe-tiny.json")) as f:
    AFMOE = json.load(f)
# ROWS x hidden reaches the fused junction kernel's floor of 512 elements
PAGE, MAX_LEN, ROWS, BUCKET, SPEC_K = 4, 32, 8, 16, 2
LIVE = (np.arange(ROWS) == 0)[:, None]      # row 0 serves, the others idle
IDS = np.random.default_rng(31).integers(1, 500, 11).tolist()


def afmoe_model():
    model = drv.model_of(AFMOE, 128)
    drv.assign(model, AFMOE, common.seed_words(2 ** 31 + 5), "float32")
    model.eval()
    return model


def llama_model():
    paddle.seed(31)
    model = llama_tiny()
    model.eval()
    return model


def dense_logits(model, ids):
    """[len(ids), vocab]: the model's own forward over the whole sequence."""
    return model(paddle.to_tensor(np.asarray([ids], np.int32))).numpy()[0]


class Programs:
    """An engine that is never started, its `ServingModel`'s forwards each
    one jitted function of (the pools, the arguments), and the page tables a
    scheduler would keep: row r owns pages 1 + r * max_pages onward, and the
    window group's entries behind a row's window are the trash page."""

    def __init__(self, model, interpret=False, **cfg):
        self.interpret = interpret
        plain = dict(prefill_chunk=8, spec_k=SPEC_K) \
            if ServingModel(model).plain else {}
        with self.mode():
            self.engine = LLMEngine(model, ServingConfig(
                page_size=PAGE, num_pages=1 + ROWS * MAX_LEN // PAGE,
                max_batch=ROWS, max_seq_len=MAX_LEN, prefix_cache=False,
                prefill_buckets=(BUCKET, MAX_LEN), **plain, **cfg))
        self.sm = self.engine._sm
        self.max_pages = MAX_LEN // PAGE
        self.tables = 1 + np.arange(ROWS * self.max_pages, dtype=np.int32) \
            .reshape(ROWS, self.max_pages)
        self._jitted = {}

    @contextlib.contextmanager
    def mode(self):
        """Interpret mode as `to_static` runs a program under it: the
        kernels dispatch, in their 32-bit world."""
        if not self.interpret:
            yield
            return
        kern.force_interpret(True)
        try:
            with kern.x64_off():
                yield
        finally:
            kern.force_interpret(False)

    def forward(self, name, *args):
        """`ServingModel.<name>_forward(*args)` as numpy, the pools kept."""
        pools = self.engine._pools()

        def pure(state, arrays):
            for p, (k, v) in zip(pools, state):
                p.k._data, p.v._data = k, v
            out = getattr(self.sm, name + "_forward")(
                *[Tensor(a) for a in arrays])
            self.sm.take_counts()
            return out._data, [(p.k._data, p.v._data) for p in pools]

        fn = self._jitted.setdefault(name, jax.jit(pure))
        state = [(p.k._data, p.v._data) for p in pools]
        try:
            with self.mode():
                out, state = fn(state, [jnp.asarray(a) for a in args])
        finally:    # a trace leaves its tracers in the pools
            for p, (k, v) in zip(pools, state):
                p.k._data, p.v._data = k, v
        return np.asarray(out)

    def window_tables(self, lengths):
        """() for a model without window layers, else (the window group's
        tables for rows holding `lengths` positions,)."""
        if self.sm.window is None:
            return ()
        from paddle_tpu.serving import kv_cache
        t = self.tables.copy()
        for r, n in enumerate(lengths):
            t[r, :kv_cache.window_first_page(n, self.sm.window, PAGE)] = 0
        return (t,)

    # each returns logits [positions, vocab] of row 0

    def prefill(self, ids):
        toks = np.zeros((1, BUCKET), np.int32)
        toks[0, :len(ids)] = ids
        return self.forward(
            "prefill", toks, np.int32(len(ids)), self.tables[0],
            *[t[0] for t in self.window_tables([len(ids)] * ROWS)])

    def decode(self, token, length):
        """Row 0's `token` at position length - 1."""
        first = LIVE[:, 0].astype(np.int32)
        return self.forward(
            "decode", token * first, (length - 1) * first,
            np.where(LIVE, self.tables, 0),
            *[np.where(LIVE, t, 0)
              for t in self.window_tables([length] * ROWS)])[:1]

    def chunk(self, ids, start):
        toks = np.zeros((1, 8), np.int32)
        toks[0, :len(ids)] = ids
        return self.forward("prefill_chunk", toks, np.int32(start),
                            np.int32(len(ids)), self.tables[0])

    def verify(self, lanes, base):
        """Row 0's last token and its drafts (`lanes`, at most SPEC_K + 1)
        from position `base` on; logits of the lanes given."""
        toks = np.zeros((ROWS, SPEC_K + 1), np.int32)
        toks[0, :len(lanes)] = lanes
        first = LIVE[:, 0].astype(np.int32)
        return self.forward(
            "verify", toks, base * first, (len(lanes) - 1) * first,
            np.where(LIVE, self.tables, 0))[0, :len(lanes)]


def int8_back(model, sm):
    """The model's dense logits over IDS with the seven linears of every
    layer holding what the int8 adapter `sm` multiplies with."""
    from paddle_tpu.nn.quant import weight_dequantize
    from paddle_tpu.serving.model import _LAYER_LINEARS, _get_path
    kept = []
    for (tag, i), (qw, scale) in sm._qweights.items():
        mod = _get_path(model.layers[i], dict(_LAYER_LINEARS)[tag])
        kept.append((mod.weight, mod.weight._data))
        mod.weight._data = weight_dequantize(
            qw, scale, algo="weight_only_int8")._data.astype(jnp.float32)
    try:
        return dense_logits(model, IDS)
    finally:
        for w, data in kept:
            w._data = data


VARIANTS = {
    "llama": lambda: (llama_model(), {}),
    "llama_fused": lambda: (llama_model(), dict(interpret=True,
                                                 fused_block=True)),
    "llama_int8": lambda: (llama_model(), dict(quant="weight_only_int8")),
    "afmoe": lambda: (afmoe_model(), {}),
}


@pytest.fixture(scope="module")
def built():
    """{variant: (Programs, dense logits of IDS)}, each built on first use."""
    made = {}

    def get(name):
        if name not in made:
            model, cfg = VARIANTS[name]()
            progs = Programs(model, **cfg)
            want = int8_back(model, progs.sm) if progs.sm.quantized \
                else dense_logits(model, IDS)
            made[name] = (progs, want)
        return made[name]

    yield get
    for progs, _ in made.values():
        progs.engine.shutdown(drain=False)


# -- (a) logits at every real position ---------------------------------------
# each run gives (logits [positions, vocab], the first of those positions)

def run_prefill(p):
    return np.concatenate(
        [p.prefill(IDS[:n]) for n in range(1, len(IDS) + 1)]), 0


def run_decode(p):
    got = [p.prefill(IDS[:5])]
    got += [p.decode(IDS[n - 1], n) for n in range(6, len(IDS) + 1)]
    return np.concatenate(got), 4      # positions 4 ..


def run_chunk(p):
    """Two chunks, [0, 6) and [6, 11): each run at every length up to its
    own, so that the last position of every length is read."""
    got = [p.chunk(IDS[:n], 0) for n in range(1, 7)]
    got += [p.chunk(IDS[6:6 + n], 6) for n in range(1, 6)]
    return np.concatenate(got), 0


def run_verify(p):
    """After a prefill of 5: three lanes from position 5, three from 8."""
    p.prefill(IDS[:5])
    return np.concatenate([p.verify(IDS[5:8], 5), p.verify(IDS[8:11], 8)]), 5


PROGRAMS = {"prefill": run_prefill, "decode": run_decode,
            "chunk": run_chunk, "verify": run_verify}


@pytest.mark.parametrize("variant,program", [
    (v, p) for v in ("llama", "llama_fused", "llama_int8") for p in PROGRAMS
] + [("afmoe", "prefill"), ("afmoe", "decode")])
def test_program_logits_match_the_dense_forward(built, variant, program):
    progs, want = built(variant)
    got, first = PROGRAMS[program](progs)
    assert got.shape == want[first:].shape
    np.testing.assert_allclose(got, want[first:], atol=TOL, rtol=0)
    if variant == "llama_fused":
        assert "block_decode_epilogue" in progs.sm.paths[program].values()


def test_a_wrong_position_shows(built):
    """The comparison above can fail: one token's KV a slot off moves a
    logit by far more than TOL."""
    progs, want = built("llama")
    progs.prefill(IDS[:5])
    got = progs.decode(IDS[5], 7)      # the token of position 5 put at 6
    assert np.abs(got[0] - want[5]).max() > 100 * TOL


# -- (b) what a model that is not plain is refused ---------------------------

@pytest.mark.parametrize("asked,why", [
    (dict(prefix_cache=True), "a hit's suffix runs as a prefill chunk"),
    (dict(prefix_cache=False, prefill_chunk=8),
     "the chunk program knows the plain Llama layer and one page table"),
    (dict(prefix_cache=False, spec_k=2),
     "the verify program knows the plain Llama layer and one page table"),
], ids=["prefix_cache", "prefill_chunk", "spec_k"])
def test_afmoe_is_refused_what_binds_one_pool(asked, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(afmoe_model(), ServingConfig(
            page_size=PAGE, num_pages=17, max_batch=2,
            max_seq_len=MAX_LEN, **asked))


# -- (c), (d): the programs' jaxprs ------------------------------------------

def program_jaxpr(progs, name):
    """The jaxpr of the engine's program `name` (forward and sampler, as
    `to_static` traces it: under the process's x64), its weights constants."""
    eng = progs.engine
    b, p = ROWS, progs.max_pages
    i32, f32 = jnp.int32, jnp.float32
    key, step = eng._key_t._data, jnp.zeros((), i32)
    wide = [jnp.zeros((b, p), i32)] * (eng.window_pool is not None)
    sf, args = {
        "decode": (eng._decode_sf, [
            jnp.zeros(b, i32), jnp.zeros(b, i32), jnp.zeros((b, p), i32),
            jnp.zeros(b, f32), key, step] + wide),
        "prefill": (eng._prefill_sf, [
            jnp.zeros((1, BUCKET), i32), jnp.ones((), i32), jnp.zeros(p, i32),
            jnp.zeros((), f32), key, step] + [w[0] for w in wide]),
        "chunk": (eng._chunk_sf, [
            jnp.zeros((1, 8), i32), jnp.zeros((), i32), jnp.ones((), i32),
            jnp.zeros(p, i32), jnp.zeros((), f32), key, step]),
        "verify": (eng._verify_sf, [
            jnp.zeros((b, SPEC_K + 1), i32), jnp.zeros(b, i32),
            jnp.zeros(b, i32), jnp.zeros((b, p), i32), jnp.zeros(b, f32),
            key, step]),
    }[name]
    pools = eng._pools()
    state = [(q.k._data, q.v._data) for q in pools]
    try:
        with progs.mode():
            return jax.make_jaxpr(
                lambda *a: sf._fn(*[Tensor(x) for x in a])._data)(*args)
    finally:
        for q, (k, v) in zip(pools, state):
            q.k._data, q.v._data = k, v


def equations(jaxpr, outer=""):
    """(equation, its whole name stack) of a jaxpr and of the jaxprs inside
    its equations, an inner stack under its equation's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (outer, str(eqn.source_info.name_stack))
                         if s)
        yield eqn, stack
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, stack)


#: 64-bit values a program may hold: (innermost of SCOPES in the equation's
#: name stack, primitives, why). The process runs under x64 (ROADMAP C12),
#: so jax's own index handling and every `jnp.arange` / `jnp.sum` /
#: `jnp.argmax` without a dtype make int64; all of it is arithmetic on a few
#: indices or counts, none on activations, and none under `head_sample`.
SCOPES = ("moe_route", "moe_experts", "kv_write", "kv_gather", "attention",
          "mlp", "head_sample")
WIDE_ALLOWED = [
    ("", {"convert_element_type", "add", "select_n", "reshape",
          "broadcast_in_dim"},
     "`x[idx]` on traced positions (each row's write page, its RoPE row): "
     "jnp widens the index, wraps a negative one (idx + size) and shapes it "
     "for the gather"),
    ("", {"reduce_sum"},
     "`take_counts`: jnp.sum over the layers' int32 counts, cast back"),
    ("kv_write", {"broadcast_in_dim"},
     "`pool.at[layer, ...]`: the layer, a Python int, beside int32 indices"),
    ("attention", {"iota", "add", "convert_element_type",
                   "broadcast_in_dim"},
     "the composite attention's masks (`jnp.arange` with no dtype); where "
     "the kernels dispatch they take these equations' place"),
    ("moe_route", {"convert_element_type", "add", "select_n", "reshape"},
     "top_k's indices through `take_along_axis`: `x[idx]` as above"),
    ("moe_experts", {"convert_element_type", "add", "sub", "select_n",
                     "reshape", "iota", "reduce_sum"},
     "the dropless grouping's positions and group sizes"),
    ("mlp", {"convert_element_type", "reduce_sum"},
     "`experts_hit`: jnp.sum(sizes > 0), cast back to int32"),
]
#: the verify program alone: `speculative.verify_tokens` is not this file's
#: subject and no cell drives it; its float64 draw is what PR 30 took out
#: of `_sample` and is named in ROADMAP C12
VERIFY_ALLOWED = [
    ("", {"argmax", "reduce_sum", "convert_element_type", "add", "select_n",
          "reshape", "broadcast_in_dim"},
     "`verify_tokens`: jnp.argmax and jnp.sum with no dtype, and `x[idx]` "
     "on the accepted count"),
    ("", {"random_bits", "shift_right_logical", "or", "bitcast_convert_type",
          "sub", "max", "mul", "jit"},
     "`verify_tokens` draws its acceptance uniform in float64 under x64"),
]


@pytest.mark.parametrize("variant,program", [
    ("llama", p) for p in PROGRAMS] + [("afmoe", "prefill"),
                                       ("afmoe", "decode")])
def test_program_holds_no_64_bit_value_unlisted(built, variant, program):
    """ROADMAP C12: the programs trace under the process's x64; what they
    compute on activations stays 32-bit, and the 64-bit index arithmetic
    that jax adds is listed above, so that a new one shows."""
    progs, _ = built(variant)
    allowed = WIDE_ALLOWED + (VERIFY_ALLOWED if program == "verify" else [])
    unlisted = {}
    for eqn, stack in equations(program_jaxpr(progs, program).jaxpr):
        parts = stack.split("/")
        scope = next((s for s in SCOPES if s in parts), "")
        for var in eqn.outvars:
            dtype = getattr(var.aval, "dtype", None)
            if dtype is None or not jnp.issubdtype(dtype, jnp.number) \
                    or dtype.itemsize < 8:
                continue
            prim = eqn.primitive.name
            floating = jnp.issubdtype(dtype, jnp.inexact)
            if floating and program != "verify" or not any(
                    scope == s and prim in prims for s, prims, _ in allowed):
                unlisted[(stack, prim)] = str(var.aval)
    assert not unlisted, unlisted


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("variant", ["llama_fused", "afmoe"])
def test_program_scopes_are_where_the_readers_look(built, variant, program):
    """`perfbench/readers/*_time_share` match `/scope/` in an op's name:
    matmuls and kernels under `attention`, `mlp` or `head_sample`, the
    writes into a pool under `kv_write`, a layer's attention under its
    group's scope."""
    progs, _ = built(variant)
    pool_shapes = {tuple(p.k._data.shape) for p in progs.engine._pools()}
    heavy, kernels, writes, groups = 0, 0, 0, set()
    for eqn, stack in equations(program_jaxpr(progs, program).jaxpr):
        parts = set(stack.split("/"))
        prim = eqn.primitive.name
        if prim in ("dot_general", "pallas_call"):
            heavy += 1
            kernels += prim == "pallas_call"
            assert parts & {"attention", "mlp", "head_sample"}, (prim, stack)
            groups |= parts & {"attention_window", "attention_global"}
            if parts & {"attention_window", "attention_global"}:
                assert "attention" in parts, stack
        if prim.startswith("scatter") and \
                tuple(eqn.outvars[0].aval.shape) in pool_shapes:
            writes += 1
            assert "kv_write" in parts, stack
    sm = progs.sm
    assert heavy and writes == 2 * len(sm._kinds)
    assert groups == ({"attention_window"} if sm.n_window_layers else set()) \
        | ({"attention_global"} if sm.n_global_layers else set())
    assert bool(kernels) == progs.interpret     # the kernels are in it


# -- (e) the kernels' cost sheets --------------------------------------------

def test_roofline_ms_uses_hbm_bandwidth():
    from paddle_tpu.cost_model.collective import CHIP_PRESETS, roofline_ms
    for spec in CHIP_PRESETS.values():
        assert spec["hbm_gbps"] > 0
    # memory-bound: 1 GB at v5e's 820 GB/s ~ 1.22 ms
    assert roofline_ms(1.0, 1e9, "v5e") == pytest.approx(1e3 / 820.0)
    # compute-bound: 197 TFLOP at 197 TFLOP/s = 1 s
    assert roofline_ms(197e12, 1, "v5e") == pytest.approx(1000.0)


@pytest.mark.parametrize("module", ["mmha_pallas", "block_fused_pallas",
                                    "moe_gemm_pallas"])
def test_kernel_cost_is_the_roofline(module):
    """Of the serving programs' own kernels: every sheet carries the chip's
    roofline over its flops and bytes, and nothing measured."""
    from paddle_tpu.cost_model import kernel_cost
    from paddle_tpu.cost_model.collective import roofline_ms
    cost = kernel_cost("paddle_tpu.ops.kernels." + module, chip="v5e")
    assert cost["kernels"]
    for sheet in cost["kernels"]:
        assert sheet["cost_source"] == "roofline"
        assert sheet["predicted_ms"] == pytest.approx(roofline_ms(
            sheet["flops"], sheet["hbm_bytes"], "v5e"))
        assert not {"measured_ms", "tuned_block",
                    "predicted_vs_measured"} & set(sheet)


# -- (f) the option is gone, not ignored -------------------------------------

def test_serving_config_has_no_fused_decode_layer():
    with pytest.raises(TypeError, match="fused_decode_layer"):
        ServingConfig(fused_decode_layer=True)
    assert len(ServingConfig.__dataclass_fields__) == 22


def test_serving_model_has_no_fused_decode_layer():
    with pytest.raises(TypeError, match="fused_decode_layer"):
        ServingModel(llama_model(), fused_decode_layer=True)
