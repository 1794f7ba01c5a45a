#!/usr/bin/env python
"""API-surface gate (reference analog: the ops-yaml regeneration check —
any op added/removed/re-signatured must update the committed manifest).

Usage:
  python tools/check_api_surface.py            # check vs api_manifest.json
  python tools/check_api_surface.py --update   # regenerate the manifest
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "api_manifest.json")


def main():
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--update", action="store_true")
    ap.add_argument("--manifest", default=MANIFEST)
    args = ap.parse_args()

    from paddle_tpu.ops.registry import check_manifest, save_manifest
    from paddle_tpu.ops import op_gen

    # the YAML registry is upstream of the manifest: generated code must be
    # current and every YAML op importable before the manifest means anything
    if not op_gen.check_up_to_date():
        print("ops/_generated.py is stale vs ops.yaml — run "
              "`python tools/gen_ops.py --write`")
        return 1
    yaml_missing = op_gen.surface_check()
    if yaml_missing:
        print(f"ops.yaml entries missing from the live surface: {yaml_missing}")
        return 1

    if args.update:
        n = save_manifest(args.manifest)
        print(f"wrote {args.manifest}: {n} public APIs")
        return 0
    if not os.path.exists(args.manifest):
        # a missing manifest must FAIL the gate — otherwise deleting the
        # file silently bypasses the API-surface check
        print(f"manifest {args.manifest} missing; run --update and commit it")
        return 1

    missing, changed, added = check_manifest(args.manifest)
    for n in missing:
        print(f"REMOVED: {n}")
    for n in changed:
        print(f"SIGNATURE CHANGED: {n}")
    if added:
        print(f"note: {len(added)} new APIs not in manifest "
              f"(run --update to record them)")
    if missing or changed:
        print("API surface check FAILED")
        return 1
    print(f"API surface OK ({len(added)} additions pending --update)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
