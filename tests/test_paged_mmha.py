"""The paged decode-attention kernel (`mmha_pallas.paged_mmha_decode`) and its
one call site (`kv_cache.paged_attention`, `ServingModel.decode_forward`).

The kernel runs in interpret mode here; its oracle is
`reference_paged_attention` over `gather_layer`. What the interpreter cannot
see (tiling, VMEM, the DMAs' shapes) is compiled in
tests/test_tpu_lowering.py and tests/test_chip_compile.py."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.kernels import _common as kern
from paddle_tpu.ops.kernels import mmha_pallas
from paddle_tpu.serving import kv_cache

PS = 16


@pytest.fixture
def interpret():
    kern.force_interpret(True)
    try:
        yield
    finally:
        kern.force_interpret(False)


def _pools(rng, dtype, layers=3, pages=24, h_kv=2, d=128):
    shape = (layers, pages, h_kv, PS, d)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _oracle(q, kp, vp, layer, tables, pos):
    return kv_cache.reference_paged_attention(
        q, kv_cache.gather_layer(kp, layer, tables),
        kv_cache.gather_layer(vp, layer, tables), pos)


def _gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("ppb", [1, 2, 4, 8])
@pytest.mark.parametrize("rep", [4, 1])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_paged_kernel_matches_the_gathered_composite(dtype, tol, rep, ppb):
    """Scattered and shared pages, a layer past the first, positions at
    0, ps-1, ps, each side of a block edge and the last of the table, and
    rows with nothing live (position 0, an all-trash table)."""
    rng = np.random.default_rng(ppb * 10 + rep)
    h_kv, d, max_pages, layer = 2, 128, 8, 2
    kp, vp = _pools(rng, dtype, h_kv=h_kv, d=d)
    bt = min(ppb, max_pages) * PS
    pos = np.asarray([0, 0, PS - 1, PS, bt - 1, min(bt, max_pages * PS - 1),
                      max_pages * PS - 1, 37], np.int32)
    b = len(pos)
    tables = np.stack([rng.permutation(np.arange(1, kp.shape[1]))[:max_pages]
                       for _ in range(b)]).astype(np.int32)
    tables[0] = kv_cache.TRASH_PAGE          # an inactive slot
    tables[7, :2] = tables[6, :2]            # two rows share a prefix
    q = jnp.asarray(rng.standard_normal((b, 1, h_kv * rep, d)), dtype)
    tab, p = jnp.asarray(tables), jnp.asarray(pos)
    got = mmha_pallas.paged_mmha_decode(q, kp, vp, jnp.int32(layer), tab, p,
                                        pages_per_block=ppb, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert _gap(got, _oracle(q, kp, vp, layer, tab, p)) <= tol


def test_paged_kernel_table_narrower_than_a_block():
    """max_pages below and not a multiple of pages_per_block."""
    rng = np.random.default_rng(5)
    kp, vp = _pools(rng, "float32")
    for max_pages, ppb in ((3, 8), (5, 2)):
        tables = np.stack([rng.permutation(np.arange(1, 24))[:max_pages]
                           for _ in range(3)]).astype(np.int32)
        pos = np.asarray([max_pages * PS - 1, PS, 5], np.int32)
        q = jnp.asarray(rng.standard_normal((3, 1, 4, 128)), jnp.float32)
        got = mmha_pallas.paged_mmha_decode(
            q, kp, vp, jnp.int32(1), jnp.asarray(tables), jnp.asarray(pos),
            pages_per_block=ppb, interpret=True)
        assert _gap(got, _oracle(q, kp, vp, 1, jnp.asarray(tables),
                                 jnp.asarray(pos))) <= 2e-5


def test_paged_attention_dispatch_forced_both_ways():
    rng = np.random.default_rng(6)
    kp, vp = _pools(rng, "float32")
    tables = np.stack([rng.permutation(np.arange(1, 24))[:4]
                       for _ in range(3)]).astype(np.int32)
    tables[1] = kv_cache.TRASH_PAGE          # an inactive slot
    tables = jnp.asarray(tables)
    pos = jnp.asarray([40, 0, 7], jnp.int32)
    q = jnp.asarray(rng.standard_normal((3, 1, 4, 128)), jnp.float32)
    kernel = kv_cache.paged_attention(q, kp, vp, 1, tables, pos,
                                      interpret=True)
    composite = kv_cache.paged_attention(q, kp, vp, 1, tables, pos,
                                         interpret=False)
    default = kv_cache.paged_attention(q, kp, vp, 1, tables, pos)
    live = jnp.asarray([0, 2])
    assert _gap(kernel[live], composite[live]) <= 2e-5
    # a slot with nothing live is fetched for and not scored
    assert _gap(kernel[1], jnp.zeros_like(kernel[1])) == 0.0
    # on the CPU the gate says no: the composite, bit for bit
    assert _gap(default, composite) == 0.0


def test_write_token_rows_writes_what_write_token_writes():
    rng = np.random.default_rng(7)
    kp, _ = _pools(rng, "float32")
    page_ids = jnp.asarray([3, 9, 0, 9], jnp.int32)
    slots = jnp.asarray([0, 15, 0, 4], jnp.int32)
    vals = jnp.asarray(rng.standard_normal((4, 2, 128)), jnp.float32)
    a = kv_cache.write_token(kp, 1, page_ids, slots, vals)
    b = kv_cache.write_token_rows(kp, 1, page_ids, slots, vals)
    assert _gap(a, b) == 0.0 and _gap(a, kp) > 0.0


@pytest.mark.parametrize("q_shape,pool_shape,dtype,want", [
    ((32, 1, 32, 128), (16, 2049, 8, 16, 128), "bfloat16", True),
    ((4, 1, 4, 128), (2, 9, 1, 8, 128), "float32", True),
    ((4, 1, 4, 256), (2, 9, 4, 16, 256), "bfloat16", True),
    ((4, 2, 4, 128), (2, 9, 1, 16, 128), "bfloat16", False),   # two tokens
    ((4, 1, 4, 64), (2, 9, 1, 16, 64), "bfloat16", False),     # half a lane row
    ((4, 1, 4, 128), (2, 9, 1, 8, 128), "bfloat16", False),    # half a bf16 tile
    ((4, 1, 4, 128), (2, 9, 1, 4, 128), "float32", False),
    ((4, 1, 3, 128), (2, 9, 2, 16, 128), "float32", False),    # ragged groups
    ((4, 1, 4, 128), (2, 9, 1, 32, 128), "int8", False),
    ((4, 1, 4, 128), (9, 1, 16, 128), "float32", False),       # a layer slice
])
def test_paged_gate(interpret, q_shape, pool_shape, dtype, want):
    assert mmha_pallas.use_paged_kernel(q_shape, pool_shape, dtype) is want
    assert kv_cache.paged_attention_path(q_shape, pool_shape, dtype) == \
        (kv_cache.PAGED_PATH if want else "composite")


def test_paged_gate_says_no_where_no_kernel_dispatches():
    assert not kern.available()
    assert kv_cache.paged_attention_path(
        (32, 1, 32, 128), (16, 2049, 8, 16, 128), "bfloat16") == "composite"
    assert kv_cache.paged_block_positions("composite", 16, 256) == 0
    assert kv_cache.paged_block_positions(kv_cache.PAGED_PATH, 16, 256) == \
        16 * mmha_pallas.PAGES_PER_BLOCK
    assert kv_cache.paged_block_positions(kv_cache.PAGED_PATH, 16, 3) == 48


# -- the engine: token-exact against the composite, and what it counts -------

# the last one crosses a block edge (64 positions) while it decodes
_PROMPTS = [[3, 5, 7, 11], list(range(1, 20)), [9] * 61]
_CFG = dict(page_size=8, num_pages=33, max_batch=4, max_new_tokens=6,
            max_seq_len=128, prefix_cache=False)


def _model():
    from paddle_tpu.models import llama_tiny
    paddle.seed(0)
    # head width 128: what the paged kernel's tiles admit
    model = llama_tiny(vocab_size=128, max_position_embeddings=128,
                       hidden_size=256, num_layers=2, num_heads=2,
                       num_kv_heads=1, intermediate_size=64)
    model.eval()
    return model


def _serve(model, paged):
    """(tokens, decode path, decode spans) of one engine's greedy run:
    the composite on the CPU as it is, the paged kernel under the
    interpreter (the only way a kernel runs off the chip)."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import LLMEngine, ServingConfig
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = True
    kern.force_interpret(paged)
    try:
        eng = LLMEngine(model, ServingConfig(**_CFG))
        try:
            reqs = [eng.submit(p) for p in _PROMPTS]
            out = [r.result(timeout=600) for r in reqs]
            path = eng.program_stats()["decode"]["path"]
            stats = eng.program_stats()["decode"]
        finally:
            eng.shutdown(drain=False)
        spans = [s for s in tracing.step_spans()["spans"]
                 if s["name"] == "serving.decode"]
    finally:
        kern.force_interpret(False)
        tr.enabled = was
        tr.reset()
    assert stats["compiles"] == 1 and stats["retraces"] == 0
    return out, path, spans


@pytest.fixture(scope="module")
def both_paths():
    model = _model()
    return _serve(model, paged=False), _serve(model, paged=True)


def test_engine_paged_path_is_token_exact_against_the_composite(both_paths):
    (ref, ref_path, _), (out, path, _) = both_paths
    assert ref_path["attention"] == "composite"
    assert path["attention"] == "paged_mmha_decode"
    assert all(len(o) == _CFG["max_new_tokens"] for o in out)
    assert out == ref


def test_gathered_is_what_each_path_reads(both_paths):
    (_, _, composite), (_, _, paged) = both_paths
    max_pages = _CFG["max_seq_len"] // _CFG["page_size"]
    block = kv_cache.paged_block_positions(kv_cache.PAGED_PATH,
                                           _CFG["page_size"], max_pages)
    assert block == 64 and composite and paged
    for s in composite:
        # every slot of every table row, whatever is live
        assert s["counts"]["gathered"] == \
            _CFG["max_batch"] * max_pages * _CFG["page_size"]
    seen = set()
    for s in paged:
        c = s["counts"]
        # each live row's positions rounded up to the kernel's block:
        # between the live positions and a block a row more
        assert c["gathered"] % block == 0
        assert c["positions"] <= c["gathered"] < c["positions"] + \
            c["rows"] * block
        seen.add(c["gathered"])
    # a number of each step, no longer of the program's shapes
    assert len(seen) > 1


def test_gathered_positions_rounds_each_live_row_to_the_block():
    from paddle_tpu.serving import LLMEngine, ServingConfig
    eng = LLMEngine(_model(), ServingConfig(**_CFG))
    try:
        assert eng.gathered_positions("decode", [5, 70]) == 0   # no call yet
        eng._sm.gathered["decode"] = (4 * 8 * 8, 0)
        assert eng.gathered_positions("decode", [5, 70]) == 256
        eng._sm.gathered["decode"] = (4 * 8 * 8, 64)
        assert eng.gathered_positions("decode", [5, 70]) == 64 + 128
        assert eng.gathered_positions("decode", [64, 65, 1]) == 64 + 128 + 64
        assert eng.gathered_positions("decode", []) == 0
    finally:
        eng.shutdown(drain=False)
