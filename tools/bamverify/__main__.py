"""CLI: ``python -m tools.bamverify [paths...]``.

Lowers the jit-cached op family at canonical bucket shapes on the CPU
backend, runs the BAM5xx rules over the compiled HLO, sweeps the
bucketed wrappers for executable leaks, and diffs the committed artifact
manifest (``tools/bamverify/manifest.json``).

Exit codes (shared convention with ``tools.bamlint``): ``0`` clean /
``--list-rules`` / ``--update-manifest``, ``1`` rule findings or
manifest drift, ``2`` usage or internal error.

``paths`` are accepted for CLI symmetry with bamlint (CI invokes both
the same way) and validated for existence, but artifact verification is
whole-program: it lowers the op family, it does not scan the files.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

from tools.bamverify import ALL_RULES
from tools.bamverify.manifest import (
    MANIFEST_PATH, diff_manifest, entry_from_stats, load_manifest,
    manifest_jax_version, save_manifest,
)
from tools.bamverify.rules import check_artifact

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.bamverify",
        description="BaM lowered-artifact verification (donation / dtype "
                    "/ callback-gating rules over compiled HLO, plus the "
                    "compiled-graph regression manifest).")
    ap.add_argument("paths", nargs="*",
                    help="accepted for symmetry with tools.bamlint; "
                         "verification always lowers the whole op family")
    ap.add_argument("--manifest", type=pathlib.Path, default=MANIFEST_PATH,
                    help="manifest file (default: tools/bamverify/"
                         "manifest.json)")
    ap.add_argument("--update-manifest", action="store_true",
                    help="record the current artifacts as the new baseline")
    ap.add_argument("--no-manifest", action="store_true",
                    help="skip the manifest diff (rules only)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in sorted(ALL_RULES):
            print(f"{rule}  {ALL_RULES[rule]}")
        return 0

    missing = [p for p in args.paths
               if not (pathlib.Path(p) if pathlib.Path(p).is_absolute()
                       else REPO_ROOT / p).exists()]
    if missing:
        print(f"bamverify: no such path(s): {', '.join(missing)}",
              file=sys.stderr)
        return 2

    try:
        # JAX import + lowering live behind the CLI entry so --list-rules
        # and usage errors never need the heavy dependency.
        from tools.bamverify.lowering import (
            collect_stats, lower_all, sweep_bucketed,
        )
        artifacts = lower_all()
        stats = collect_stats(artifacts)
    except Exception as e:                      # lowering is internal
        print(f"bamverify: internal error while lowering the op family: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2

    current = {key: entry_from_stats(s) for key, s in stats.items()}
    import jax
    if args.update_manifest:
        save_manifest(current, args.manifest, jax_version=jax.__version__)
        print(f"bamverify: wrote {len(current)} artifact entr(ies) to "
              f"{args.manifest}")

    recorded = {} if args.no_manifest else load_manifest(args.manifest)
    findings = []
    for spec, _txt in artifacts:
        findings.extend(check_artifact(
            spec, stats[spec.key], recorded.get(spec.key)))
    findings.extend(sweep_bucketed())

    drift = [] if (args.no_manifest or args.update_manifest) \
        else diff_manifest(recorded, current)

    for f in findings:
        print(f.render())
    for line in drift:
        print(f"manifest drift: {line}")
    lowered_under = manifest_jax_version(args.manifest)
    if drift and lowered_under != jax.__version__:
        print(f"bamverify: the manifest was lowered under jax "
              f"{lowered_under}, this is jax {jax.__version__}: "
              "read the drift as a toolchain change before regenerating")
    n = len(findings) + len(drift)
    if n:
        print(f"\nbamverify: {len(findings)} rule finding(s), "
              f"{len(drift)} manifest drift line(s) across "
              f"{len(artifacts)} artifact(s)")
        return 1
    print(f"bamverify: clean ({len(artifacts)} artifacts verified"
          + ("" if args.no_manifest else ", manifest matches") + ")")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
