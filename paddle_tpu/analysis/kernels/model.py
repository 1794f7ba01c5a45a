"""Kernel model extraction: from ``pk_examples()`` to ``KernelModel``s.

Every kernel module under ``ops/kernels`` exposes ``pk_examples()`` — a
list of ``(label, fn, args, kwargs)`` representative invocations (args
are ``jax.ShapeDtypeStruct``s or small concrete arrays). The extractor
traces each invocation with ``jax.make_jaxpr`` under the package's own
environment discipline (``x64_off()`` + ``force_dispatch(True)``, so the
REAL ``pallas_call`` path traces even on CPU and nothing is ever lowered
through Mosaic or executed), inlines call-like primitives, and turns
every ``pallas_call`` equation it finds into a :class:`KernelModel`:
concrete grid, per-ref block shapes, evaluable index-map jaxprs, scratch
avals and the body jaxpr. The PK rules and the resource sheets both
consume this model — extraction happens once per example.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import math
import os
from typing import Any

__all__ = ["BlockInfo", "KernelModel", "ExtractionNote",
           "extract_callable", "extract_module", "load_kernel_module",
           "GRID_ENUM_CAP"]

#: full grid enumeration (coverage / overlap / bounds) is capped here;
#: larger grids get corner-sampled bounds checks only, with an info note
GRID_ENUM_CAP = 8192


@dataclasses.dataclass
class ExtractionNote:
    """Why a file/example could not be (fully) modelled."""
    file: str
    label: str
    message: str
    failed: bool = False         # True: the module HAS examples and one broke


@dataclasses.dataclass
class BlockInfo:
    """One ref's BlockSpec as traced: shapes, dtype, evaluable index map."""
    origin: str                  # "x_ref" / "outputs" per the BlockSpec
    block_shape: tuple           # ints; Mapped/squeezed dims count as 1
    array_shape: tuple
    dtype: Any
    index_map_jaxpr: Any         # ClosedJaxpr (grid ids + prefetch refs)
    is_output: bool
    position: int                # operand position within inputs/outputs
    in_hbm: bool = False         # left in HBM (memory_space ANY/HBM): the
    #                              kernel fetches from it with its own DMAs

    @property
    def nblocks(self) -> tuple:
        """Blocks per dim: ``ceil(array_dim / block_dim)``."""
        return tuple(max(1, math.ceil(a / b))
                     for a, b in zip(self.array_shape, self.block_shape))

    @property
    def block_bytes(self) -> int:
        """Bytes of one block in VMEM: none for an operand left in HBM
        (what the kernel fetches of it lands in scratch, counted there)."""
        if self.in_hbm:
            return 0
        n = 1
        for b in self.block_shape:
            n *= int(b)
        return n * self.dtype.itemsize

    @property
    def array_bytes(self) -> int:
        n = 1
        for a in self.array_shape:
            n *= int(a)
        return n * self.dtype.itemsize

    @property
    def has_tail(self) -> bool:
        """True when some dim is not block-divisible (a padded tail
        block hangs past the array edge)."""
        return any(a % b for a, b in zip(self.array_shape,
                                         self.block_shape))

    def eval_index(self, step_ids) -> tuple | None:
        """Block indices this map yields at one grid step, or ``None``
        when the map cannot be host-evaluated (e.g. it dereferences a
        scalar-prefetch ref — data-dependent blocking)."""
        import numpy as np

        import jax

        from ...ops.kernels._common import x64_off

        cj = self.index_map_jaxpr
        invars = cj.jaxpr.invars
        # the map jaxpr was traced under x64_off (i32 literals); evaluate
        # under the same discipline with i32 step ids, or any arithmetic
        # in the map (i + 1, i // g) binds i32 against the framework's
        # global-x64 weak i64 and fails MLIR verification
        args = [np.int32(s) for s in step_ids]
        for v in invars[len(args):]:
            aval = v.aval
            shape = tuple(getattr(aval, "shape", ()) or ())
            args.append(np.zeros(shape, dtype=np.dtype(
                getattr(aval, "dtype", np.int32))))
        try:
            with x64_off():
                out = jax.core.eval_jaxpr(cj.jaxpr, cj.consts,
                                          *args[:len(invars)])
        except Exception:
            return None
        try:
            return tuple(int(x) for x in out)
        except Exception:
            return None


@dataclasses.dataclass
class KernelModel:
    """One ``pallas_call`` site, fully concretized by one example."""
    name: str                    # pallas_call name= or kernel body name
    label: str                   # pk_examples() label that reached it
    file: str                    # kernel module file (finding anchor)
    line: int                    # pallas_call call-site line if known
    grid: tuple
    inputs: list                 # list[BlockInfo]
    outputs: list                # list[BlockInfo]
    scratch_avals: list          # AbstractMemoryRef for scratch operands
    num_scalar_prefetch: int
    prefetch_avals: list         # avals of the scalar-prefetch operands
    body: Any                    # the kernel body Jaxpr
    input_refs: list             # body invars backing the input blocks
    output_refs: list            # body invars backing the output blocks
    scratch_refs: list
    prefetch_refs: list

    @property
    def steps(self) -> int:
        n = 1
        for g in self.grid:
            n *= int(g)
        return max(1, n)

    @property
    def enumerable(self) -> bool:
        return self.steps <= GRID_ENUM_CAP

    def grid_steps(self):
        """Row-major enumeration of grid index tuples — the TPU executes
        the grid sequentially in exactly this order, which is what makes
        the consecutive-revisit accumulation pattern legal."""
        import itertools
        if not self.grid:
            yield ()
            return
        yield from itertools.product(*(range(int(g)) for g in self.grid))


def _block_dims(block_shape, array_shape):
    """Ints per dim: Mapped/None/sentinel dims are size-1 blocks."""
    if block_shape is None:
        return tuple(int(d) for d in array_shape)
    out = []
    for b, a in zip(block_shape, array_shape):
        out.append(int(getattr(b, "block_size", 1)))
    return tuple(out)


def _memory_space(aval) -> str:
    ms = getattr(aval, "memory_space", None)
    return str(ms).lower() if ms is not None else "any"


def iter_pallas_eqns(jaxpr_like):
    """Yield every ``pallas_call`` eqn reachable through call-like
    primitives (jit / custom_vjp / remat / scan / while / cond ...)."""
    from ..graph.ir import _INLINE_PARAMS, _as_open
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim == "pallas_call":
                yield eqn
                continue
            key = _INLINE_PARAMS.get(prim)
            if key is not None and key in eqn.params:
                sub = eqn.params[key]
                yield from walk(getattr(sub, "jaxpr", sub))
                continue
            for p in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                      "branches"):
                sub = eqn.params.get(p)
                if sub is None:
                    continue
                subs = sub if isinstance(sub, (tuple, list)) else (sub,)
                for s in subs:
                    yield from walk(getattr(s, "jaxpr", s))

    yield from walk(_as_open(jaxpr_like)[0])


def _model_from_eqn(eqn, label: str, file: str) -> KernelModel:
    gm = eqn.params["grid_mapping"]
    body = eqn.params["jaxpr"]
    body = getattr(body, "jaxpr", body)
    from ..graph.ir import _user_frame, pallas_kernel_name
    name = pallas_kernel_name(eqn, "kernel")

    line = 0
    try:
        _, line = _user_frame(eqn.source_info,
                              prefer_file=os.path.abspath(file))
        line = int(line)
    except Exception:
        pass

    n_pref = int(getattr(gm, "num_index_operands", 0) or 0)
    n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
    n_in = int(getattr(gm, "num_inputs",
                       len(gm.block_mappings) - 1) or 0)
    mappings = list(gm.block_mappings)

    def info(bm, is_output, pos):
        arr = bm.array_aval
        space = getattr(getattr(bm, "block_aval", None), "memory_space",
                        None)
        return BlockInfo(
            origin=str(getattr(bm, "origin", "") or ""),
            block_shape=_block_dims(bm.block_shape, arr.shape),
            array_shape=tuple(int(d) for d in arr.shape),
            dtype=arr.dtype,
            index_map_jaxpr=bm.index_map_jaxpr,
            is_output=is_output,
            position=pos,
            in_hbm=space is not None and str(space).lower() in ("any",
                                                                "hbm"))

    inputs = [info(bm, False, i) for i, bm in enumerate(mappings[:n_in])]
    outputs = [info(bm, True, i) for i, bm in enumerate(mappings[n_in:])]

    invars = list(body.invars)
    prefetch_refs = invars[:n_pref]
    rest = invars[n_pref:]
    input_refs = rest[:len(inputs)]
    output_refs = rest[len(inputs):len(inputs) + len(outputs)]
    scratch_refs = rest[len(inputs) + len(outputs):]
    if n_scratch and len(scratch_refs) != n_scratch:
        scratch_refs = invars[len(invars) - n_scratch:]

    pref_avals = [getattr(e.aval, "inner_aval", e.aval)
                  for e in eqn.invars[:n_pref]]

    return KernelModel(
        name=name, label=label, file=file, line=line,
        grid=tuple(int(g) for g in gm.grid),
        inputs=inputs, outputs=outputs,
        scratch_avals=[v.aval for v in scratch_refs],
        num_scalar_prefetch=n_pref,
        prefetch_avals=pref_avals,
        body=body,
        input_refs=input_refs, output_refs=output_refs,
        scratch_refs=scratch_refs, prefetch_refs=prefetch_refs)


def extract_callable(fn, args=(), kwargs=None, label: str = "",
                     file: str = "") -> list:
    """Trace one example invocation and model every pallas_call in it.

    The trace runs under ``x64_off()`` (the package-wide Mosaic int-width
    discipline) with ``force_dispatch(True)`` so wrappers take their real
    kernel path off-TPU. Trace only — nothing is lowered or executed."""
    import jax

    from ...ops.kernels import _common as kcommon

    kwargs = dict(kwargs or {})
    prev = kcommon._FORCE_DISPATCH
    kcommon.force_dispatch(True)
    try:
        with kcommon.x64_off():
            closed = jax.make_jaxpr(
                lambda *a: fn(*a, **kwargs))(*args)
    finally:
        kcommon.force_dispatch(prev)
    return [_model_from_eqn(eqn, label, file)
            for eqn in iter_pallas_eqns(closed)]


def load_kernel_module(path: str):
    """Import a kernel module by file path — via its real package name
    when it lives under ``paddle_tpu`` (so relative imports and module
    identity work), falling back to a spec load."""
    path = os.path.abspath(path)
    parts = path.replace("\\", "/").split("/")
    if "paddle_tpu" in parts:
        modname = ".".join(parts[parts.index("paddle_tpu"):])
        modname = modname[:-3] if modname.endswith(".py") else modname
        try:
            return importlib.import_module(modname)
        except Exception:
            pass
    spec = importlib.util.spec_from_file_location(
        os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def extract_module(path: str):
    """(models, notes) for one kernel module file.

    A module without ``pk_examples()`` yields no models and one note
    (the CLI surfaces it at info severity). A module that fails to import,
    or an example that fails to trace, yields a ``failed`` note naming it
    (an error finding: an analyzer that modelled nothing must not read
    "clean"); the remaining examples still analyze."""
    models: list = []
    notes: list = []
    try:
        mod = load_kernel_module(path)
    except Exception as e:
        notes.append(ExtractionNote(
            path, "", f"module import failed: {type(e).__name__}: {e}",
            failed=True))
        return models, notes
    examples = getattr(mod, "pk_examples", None)
    if examples is None:
        notes.append(ExtractionNote(
            path, "", "no pk_examples(): pallas_call sites not modelled "
            "(AST rules only)"))
        return models, notes
    try:
        entries = examples()
    except Exception as e:
        notes.append(ExtractionNote(
            path, "pk_examples",
            f"pk_examples() raised: {type(e).__name__}: {e}", failed=True))
        return models, notes
    for entry in entries:
        label, fn, args, kwargs = (tuple(entry) + ((), None))[:4]
        try:
            models.extend(extract_callable(fn, args, kwargs,
                                           label=label, file=path))
        except Exception as e:
            notes.append(ExtractionNote(
                path, label,
                f"example trace failed: {type(e).__name__}: {e}",
                failed=True))
    return models, notes
