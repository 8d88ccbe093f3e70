"""The compiled-graph regression manifest (JAX-free half).

``tools/bamverify/manifest.json`` records, per op x bucket, the
structural facts of every steady-state executable the BaM hot path
ships: serial scatter count, while-loop count, donation alias count,
dtypes present, and total instruction count.  It is the compiled-artifact
analogue of bamlint's ``baseline.json``: CI re-lowers the op family and
*diffs* the manifest, so a perf-relevant change to what XLA emits — a
scatter unfused, a donation dropped, a dtype widened, an executable
ballooning — fails structurally, without timing a single op.

Refresh after a deliberate hot-path change with::

    python -m tools.bamverify --update-manifest
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

from tools.bamverify.rules import ArtifactStats

MANIFEST_PATH = pathlib.Path(__file__).resolve().parent / "manifest.json"

FIELDS = ("scatters", "while_loops", "donation_aliases", "dtypes",
          "instructions")


def entry_from_stats(stats: ArtifactStats) -> Dict:
    return {
        "scatters": stats.scatters,
        "while_loops": stats.while_loops,
        "donation_aliases": stats.donation_aliases,
        "dtypes": list(stats.dtypes),
        "instructions": stats.instructions,
    }


def load_manifest(path: pathlib.Path = MANIFEST_PATH) -> Dict[str, Dict]:
    if not path.is_file():
        return {}
    data = json.loads(path.read_text())
    return data.get("ops", {})


def manifest_jax_version(path: pathlib.Path = MANIFEST_PATH) -> str | None:
    """The JAX version the manifest's artifacts were lowered under."""
    if not path.is_file():
        return None
    return json.loads(path.read_text()).get("jax")


def save_manifest(entries: Dict[str, Dict],
                  path: pathlib.Path = MANIFEST_PATH, *,
                  jax_version: str) -> None:
    payload = {"version": 1, "jax": jax_version,
               "ops": {k: entries[k] for k in sorted(entries)}}
    path.write_text(json.dumps(payload, indent=2) + "\n")


def diff_manifest(recorded: Dict[str, Dict],
                  current: Dict[str, Dict]) -> List[str]:
    """Readable per-op x bucket drift report (empty = manifests agree).

    Every line names the artifact and the field that moved — never a raw
    JSON dump — so a CI failure reads as "submit[donated]@64: scatters
    14 -> 17", not as a wall of text.
    """
    out: List[str] = []
    for key in sorted(set(recorded) | set(current)):
        if key not in current:
            out.append(f"{key}: recorded in the manifest but no longer "
                       "lowered (op removed or renamed? run "
                       "--update-manifest)")
            continue
        if key not in recorded:
            out.append(f"{key}: lowered but missing from the manifest "
                       "(new op/bucket — run --update-manifest)")
            continue
        rec, cur = recorded[key], current[key]
        for f in FIELDS:
            rv, cv = rec.get(f), cur.get(f)
            if rv != cv:
                out.append(f"{key}: {f} {rv} -> {cv}")
    return out
