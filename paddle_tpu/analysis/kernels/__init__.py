"""paddle_tpu.analysis.kernels — the Pallas kernel analysis tier.

Fourth tier of the analysis stack (AST trace-safety TS0xx, jaxpr graph
GA1xx, lock discipline CS1xx, and now kernel safety PK2xx): every
hand-written Pallas kernel under ``ops/kernels`` is statically verified
BEFORE it ever reaches Mosaic, and statically COSTED so the cost model
and the future block-shape autotuner know what a launch holds resident
and moves.

**Model plane** (:mod:`.model` → :mod:`.rules`, ids PK200-PK204/207-209):
each kernel module's ``pk_examples()`` invocations are traced (never
lowered or executed) and every reached ``pallas_call`` becomes a
:class:`~.model.KernelModel` — concrete grid, block shapes, evaluable
index maps, scratch, body jaxpr. Rules then check VMEM residency
against ``cost_model.chip_vmem_bytes()``, output coverage / overlap /
bounds by abstract evaluation over the real grid, tail masking,
custom_vjp accumulation dtype discipline, prefetch misuse and dead
operands.

**AST plane** (PK206): a ``pallas_call`` outside ``x64_off()``.

**Resource sheets** (:mod:`.resources`): per-kernel static VMEM
bytes/step, FLOPs, HBM bytes and arithmetic intensity, exported as
``cost_model.kernel_cost(...)`` — the admissibility filter the
autotuner applies before any measured trial, and the static half of
``bench.py``'s ``extra.kernel_static`` cross-validation.

Entry points:

* ``python -m paddle_tpu.analysis.kernels <paths>`` — house-style CLI
  (``--format json``/``--select``/``--min-severity``/``--list-rules``),
  exit 1 on unwaived error findings. Waivers live in
  ``tools/pk_allowlist.txt`` (auto-discovered walking up from the
  analyzed paths), one ``<file-suffix> <rule>`` per line with a
  justification comment.
* ``python -m paddle_tpu.analysis.kernels.demo`` — a planted-violation
  module tripping every ERROR-severity PK rule, analyzed on itself.
* ``tools/lint_examples.py`` kernel gate — the tier self-applied over
  the shipped kernel tree in CI.
"""

from __future__ import annotations

import os

from ..diagnostics import ERROR, INFO, Finding  # noqa: F401
from .model import (GRID_ENUM_CAP, BlockInfo, ExtractionNote,  # noqa: F401
                    KernelModel, extract_callable, extract_module)
from .resources import ResourceSheet, resource_sheet  # noqa: F401
from .rules import RULES, Rule, check_model, check_source  # noqa: F401

__all__ = [
    "RULES", "Rule", "check_model", "check_source",
    "KernelModel", "BlockInfo", "ResourceSheet", "resource_sheet",
    "extract_callable", "extract_module",
    "analyze_paths", "collect", "kernel_cost", "has_errors",
    "ALLOWLIST_NAME", "GRID_ENUM_CAP",
]

ALLOWLIST_NAME = os.path.join("tools", "pk_allowlist.txt")


def _has_pallas_call(source: str) -> bool:
    """Cheap gate: only modules that syntactically call ``pallas_call``
    are worth importing/tracing."""
    import ast

    from .rules import _call_name
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return False
    return any(isinstance(n, ast.Call)
               and _call_name(n) == "pallas_call"
               for n in ast.walk(tree))


def collect(paths, chip=None):
    """(findings, sheets) over every .py file under the given paths.

    Both planes run per file; kernel modules additionally get modelled
    through their ``pk_examples()`` and costed. A module with
    ``pallas_call`` sites but no ``pk_examples()`` yields an
    info-severity PK209 note — unmodelled kernels are visible, never
    silently skipped."""
    from ...cost_model.collective import chip_vmem_bytes
    from ..engine import _iter_py_files
    budget = chip_vmem_bytes(chip)
    findings: list = []
    sheets: list = []
    seen_sheets = set()
    for path in _iter_py_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                src = f.read()
        except OSError:
            continue
        findings.extend(check_source(src, path))
        if not _has_pallas_call(src):
            continue
        models, notes = extract_module(path)
        for note in notes:
            findings.append(Finding(
                rule_id="PK209", severity=ERROR if note.failed else INFO,
                message=(f"[{note.label}] " if note.label else "")
                + note.message,
                file=note.file,
                hint="repair the example: an unmodelled kernel is unchecked"
                if note.failed else
                "add pk_examples() so the tier can model and cost "
                "this module's kernels"))
        for m in models:
            sheet = resource_sheet(m, budget)
            key = (m.name, m.grid, sheet.block_bytes,
                   sheet.scratch_bytes)
            if key not in seen_sheets:
                seen_sheets.add(key)
                sheets.append(sheet)
            check_model(m, sheet, findings)
    uniq, seen = [], set()
    for f in findings:
        key = (f.rule_id, f.file, f.line, f.severity, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    uniq.sort(key=lambda f: f.sort_key())
    return uniq, sheets


def analyze_paths(paths, chip=None) -> list:
    """Findings only (the CLI/gate surface; sheets ride :func:`collect`
    and :func:`kernel_cost`)."""
    return collect(paths, chip=chip)[0]


def has_errors(findings) -> bool:
    return any(f.severity == ERROR for f in findings)


def kernel_cost(module_or_path, chip=None) -> dict:
    """Static resource sheets for one kernel module (the
    ``cost_model.kernel_cost`` implementation).

    Accepts a module object, a dotted module name, or a file path.
    Returns ``{module, chip, vmem_budget, kernels: [sheet...],
    notes: [...]}`` — ``kernels`` entries follow the
    :class:`~.resources.ResourceSheet` schema."""
    import importlib

    from ...cost_model.collective import chip_name, chip_vmem_bytes
    if hasattr(module_or_path, "__file__"):
        path = module_or_path.__file__
    elif os.path.sep in str(module_or_path) \
            or str(module_or_path).endswith(".py"):
        path = str(module_or_path)
    else:
        path = importlib.import_module(str(module_or_path)).__file__
    chip = chip_name(chip)
    budget = chip_vmem_bytes(chip)
    models, notes = extract_module(path)
    return {
        "module": os.path.basename(path),
        "chip": chip,
        "vmem_budget": budget,
        "kernels": [_with_roofline(resource_sheet(m, budget).to_dict(),
                                   chip) for m in models],
        "notes": [f"[{n.label}] {n.message}" if n.label else n.message
                  for n in notes],
    }


def _with_roofline(sheet: dict, chip_name: str) -> dict:
    """Every sheet gains ``predicted_ms`` (the chip roofline over the
    static flops/hbm figures) and ``cost_source: "roofline"``."""
    from ...cost_model.collective import roofline_ms
    sheet["predicted_ms"] = roofline_ms(
        sheet.get("flops", 0.0), sheet.get("hbm_bytes", 0), chip_name)
    sheet["cost_source"] = "roofline"
    return sheet
