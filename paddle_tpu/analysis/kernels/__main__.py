"""CLI: ``python -m paddle_tpu.analysis.kernels <paths>``.

Lints Pallas kernel modules with the PK200-PK209 rules and prints each
modelled kernel's static resource sheet; exits nonzero when any
error-severity finding remains after filtering and allowlisting — the
CI-gate contract ``tools/lint_examples.py``'s kernel gate builds on.
Waivers (each with a one-line justification) live in
``tools/pk_allowlist.txt``; the chip preset whose VMEM budget applies
comes from ``$PADDLE_TPU_CHIP`` (default ``v5e``). Flags, waiver
handling and exit codes come from the shared driver (:mod:`..cli`).
"""

from __future__ import annotations

import os
import sys

from ..cli import run_lint_cli
from . import ALLOWLIST_NAME, RULES, collect


def main(argv=None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    state = {"sheets": []}

    def analyze(paths):
        findings, sheets = collect(paths)
        state["sheets"] = sheets
        return findings

    def payload_extra(args):
        return {"resource_sheets": [s.to_dict()
                                    for s in state["sheets"]]}

    def text_extra(args):
        sheets = state["sheets"]
        if not sheets:
            return None
        lines = ["resource sheets (static, per grid step):"]
        for s in sheets:
            fits = "fits" if s.fits_vmem else "OVER"
            lines.append(
                f"  {s.kernel} [{s.label}] grid={s.grid} "
                f"vmem={s.vmem_bytes:,}B/{s.vmem_budget:,}B ({fits})  "
                f"flops={s.flops:.3g}  hbm={s.hbm_bytes:,}B  "
                f"AI={s.arithmetic_intensity}")
        return "\n".join(lines)

    return run_lint_cli(
        argv,
        prog="python -m paddle_tpu.analysis.kernels",
        description="Pallas kernel analyzer: VMEM residency, output "
                    "coverage/overlap, index-map bounds, x64 "
                    "discipline and dtype discipline over the kernels' "
                    "pk_examples() traces, plus static resource sheets "
                    "(docs/static_analysis.md#kernel-tier).",
        rules=RULES,
        analyze=analyze,
        allowlist_name=ALLOWLIST_NAME,
        select_example="PK200,PK203",
        positional_help="kernel .py files or directories "
                        "(e.g. paddle_tpu/ops/kernels/)",
        payload_extra=payload_extra,
        text_extra=text_extra)


if __name__ == "__main__":
    sys.exit(main())
