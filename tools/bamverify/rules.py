"""BAM5xx rules over lowered artifacts (compiled HLO text).

Everything here is JAX-free: the rules consume HLO *text* (reusing the
instruction walk of :mod:`repro.launch.hlo_analysis`), so the whole rule
engine — including the committed golden fixtures under
``tools/bamverify/fixtures/`` — runs without compiling anything.  Only
:mod:`tools.bamverify.lowering` (which produces fresh artifacts from the
live op family) needs JAX.

An artifact is one compiled executable of one op at one canonical bucket
shape, described by :class:`ArtifactSpec` (what the op *declared*:
donation, purity contract) and measured into :class:`ArtifactStats`
(what XLA *emitted*: aliasing, dtypes, callbacks, scatters).  The rules
compare the two — plus, for BAM504, the committed manifest baseline.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re
import sys
from typing import Dict, List, Optional, Tuple

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(REPO_ROOT / "src") not in sys.path:      # repro is a src-layout pkg
    sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.launch import hlo_analysis as H      # noqa: E402  (stdlib-only)

RULES = {
    "BAM501": "donation declared but the executable carries no "
              "input/output buffer aliasing — XLA silently dropped the "
              "donation, so every round copies the multi-MB state",
    "BAM502": "f64 instruction in a compiled hot-path executable "
              "(dtype creep that BAM303 could not see past lowering)",
    "BAM503": "host-callback custom-call executes unconditionally in an "
              "executable whose all-hit fast path must stay pure "
              "(the lax.cond fetch or write-back gate was compiled away "
              "or bypassed)",
    "BAM504": "serial scatter count above the recorded manifest baseline "
              "(a packed-scatter fusion regressed into per-field scatters)",
    "BAM505": "bucketed op compiled more executables than configured "
              "buckets (shape bucketing is leaking one executable per "
              "ragged batch size)",
}

# Host callbacks (jax.pure_callback / io_callback) lower to custom-calls
# whose target embeds "callback" on every backend we lower on.
CALLBACK_TARGET_MARKER = "callback"

# XLA:CPU lowers jnp scatter updates to scatter OR dynamic-update-slice
# (post-fusion); both serialize on CPU, so the "serial scatter" metric the
# PR 8 packed-scatter work optimized counts both forms.
SCATTER_OPS = ("scatter", "dynamic-update-slice")

_DTYPE_RE = re.compile(
    r"\b(pred|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64|f8e4m3fn|f8e5m2|f8e4m3|"
    r"f8e3m4|f16|bf16|f32|f64|c64|c128)\[")
_ALIAS_ENTRY_RE = re.compile(r"(?:may|must)-alias")


@dataclasses.dataclass(frozen=True)
class ArtifactSpec:
    """What one lowered op *declared* (vs what XLA emitted)."""

    op: str                     # "submit[donated]", "wait", ...
    bucket: int                 # canonical wavefront size it was lowered at
    donated: bool = False       # jit carried donate_argnums for the state
    declared_donated: int = 0   # donated pytree leaves handed to jit
    pure_all_hit: bool = False  # callbacks must stay cond-gated (BAM503)
    traced_f64: bool = False    # f64 in the PRE-optimization lowering
                                # (jaxpr/StableHLO side): catches dtype
                                # creep even when XLA DCE'd the f64 op out
                                # of the final executable (BAM502)

    @property
    def key(self) -> str:
        return f"{self.op}@{self.bucket}"


@dataclasses.dataclass
class ArtifactStats:
    """Structural census of one compiled executable's HLO text."""

    scatters: int
    while_loops: int
    donation_aliases: int
    dtypes: List[str]
    instructions: int
    custom_call_targets: List[str]
    ungated_callbacks: List[str]    # callback targets outside any cond gate


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    key: str                    # artifact key ("submit[donated]@64") or op
    message: str

    def render(self) -> str:
        return f"{self.key}: {self.rule} {self.message}"


def analyze_artifact(hlo_text: str) -> ArtifactStats:
    """Measure the structural facts the BAM5xx rules and the manifest
    consume, with one parse of the compiled HLO text."""
    comps, entry = H.parse_computations(hlo_text)
    n_instr = 0
    n_scatter = 0
    n_while = 0
    for instrs in comps.values():
        for ins in instrs:
            n_instr += 1
            if ins.op in SCATTER_OPS:
                n_scatter += 1
            elif ins.op == "while":
                n_while += 1

    # input/output aliasing lives on the HloModule header (first line).
    header = hlo_text.splitlines()[0] if hlo_text else ""
    m = re.search(r"input_output_alias=\{(.*)$", header)
    n_alias = len(_ALIAS_ENTRY_RE.findall(m.group(1))) if m else 0

    dtypes = sorted({dm.group(1) for dm in _DTYPE_RE.finditer(hlo_text)})

    calls = H.iter_custom_calls(comps)
    targets = sorted({ins.custom_call_target for _, ins in calls})
    ungated_comps = H.ungated_computations(comps, entry)
    ungated = sorted({
        ins.custom_call_target for cname, ins in calls
        if CALLBACK_TARGET_MARKER in ins.custom_call_target
        and cname in ungated_comps})
    return ArtifactStats(
        scatters=n_scatter, while_loops=n_while, donation_aliases=n_alias,
        dtypes=dtypes, instructions=n_instr,
        custom_call_targets=targets, ungated_callbacks=ungated)


def check_artifact(spec: ArtifactSpec, hlo_text_or_stats,
                   baseline: Optional[Dict] = None) -> List[Finding]:
    """Run BAM501-BAM504 against one artifact.

    ``baseline`` is this artifact's committed manifest entry (or ``None``
    when there is nothing recorded yet — BAM504 then has no baseline to
    regress against and stays silent; the manifest *diff* still reports
    the missing entry).
    """
    stats = hlo_text_or_stats
    if isinstance(stats, str):
        stats = analyze_artifact(stats)
    out: List[Finding] = []
    if spec.donated and stats.donation_aliases == 0:
        out.append(Finding(
            "BAM501", spec.key,
            f"declared donation of {spec.declared_donated} state buffer(s) "
            "but the executable has no input/output aliasing — the "
            "donation was silently dropped (every round copies the state; "
            "check for shape/dtype mismatches between the donated input "
            "and the outputs)"))
    if "f64" in stats.dtypes or spec.traced_f64:
        where = ("compiled graph contains f64 instructions"
                 if "f64" in stats.dtypes else
                 "traced program contains f64 (optimized away in the "
                 "final executable, but the creep is live in source)")
        out.append(Finding(
            "BAM502", spec.key,
            f"{where} — a dtype-less constructor or x64 promotion "
            "survived lowering"))
    if spec.pure_all_hit and stats.ungated_callbacks:
        out.append(Finding(
            "BAM503", spec.key,
            "host callback custom-call(s) "
            f"{stats.ungated_callbacks} execute unconditionally — the "
            "all-hit fast path would pay a host round-trip every round; "
            "the fetch and the write-back must stay behind their "
            "lax.cond gates"))
    if baseline is not None and stats.scatters > int(baseline["scatters"]):
        out.append(Finding(
            "BAM504", spec.key,
            f"serial scatter count {stats.scatters} exceeds the manifest "
            f"baseline {baseline['scatters']} — a packed scatter was "
            "split back into per-field updates; if intentional, run "
            "--update-manifest"))
    return out


def check_executable_count(op: str, n_buckets: int,
                           n_executables: int) -> List[Finding]:
    """BAM505: a bucketed op's jit cache may hold at most one executable
    per configured bucket; more means ragged batch sizes are leaking
    past the bucket padding and compiling per-size."""
    if n_executables > n_buckets:
        return [Finding(
            "BAM505", op,
            f"{n_executables} executables compiled for {n_buckets} "
            "configured buckets — ragged wavefronts are bypassing the "
            "bucket padding (one compile per batch size)")]
    return []


# ------------------------------------------------------------- fixtures
FIXTURE_HEADER = "bamverify-fixture:"


def parse_fixture_header(line: str) -> Tuple[str, Dict[str, int]]:
    """``// bamverify-fixture: expect BAM501 donated=17 pure_all_hit=0
    baseline_scatters=3`` -> ``("BAM501", {kwargs})``.  ``expect clean``
    marks a good fixture."""
    if FIXTURE_HEADER not in line:
        raise ValueError(f"not a bamverify fixture header: {line!r}")
    tail = line.split(FIXTURE_HEADER, 1)[1].split()
    if not tail or tail[0] != "expect":
        raise ValueError(f"fixture header missing 'expect': {line!r}")
    expected = tail[1]
    meta = {}
    for kv in tail[2:]:
        k, _, v = kv.partition("=")
        meta[k] = int(v)
    return expected, meta


def check_fixture(path: pathlib.Path) -> Tuple[str, List[Finding]]:
    """Run the rules against one committed golden fixture.

    ``.hlo`` fixtures carry a header comment describing the artifact's
    declared contract; ``.json`` fixtures feed the non-textual rules
    (BAM505's executable-count record).  Returns ``(expected_rule,
    findings)`` where expected is a rule id or ``"clean"``.
    """
    if path.suffix == ".json":
        data = json.loads(path.read_text())
        return data["expect"], check_executable_count(
            data["op"], data["n_buckets"], data["n_executables"])
    text = path.read_text()
    first, _, body = text.partition("\n")
    expected, meta = parse_fixture_header(first)
    spec = ArtifactSpec(
        op=path.stem, bucket=meta.get("bucket", 0),
        donated=bool(meta.get("donated", 0)),
        declared_donated=meta.get("donated", 0),
        pure_all_hit=bool(meta.get("pure_all_hit", 0)),
        traced_f64=bool(meta.get("traced_f64", 0)))
    baseline = None
    if "baseline_scatters" in meta:
        baseline = {"scatters": meta["baseline_scatters"]}
    return expected, check_artifact(spec, body, baseline)
