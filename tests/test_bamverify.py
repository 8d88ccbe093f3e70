"""Self-tests for the bamverify lowered-artifact analysis suite.

Two halves, mirroring the package: the JAX-free rule engine is pinned by
the committed golden fixtures under ``tools/bamverify/fixtures`` (each
``bad/`` artifact triggers exactly its rule, each ``good/`` one is
clean), and the live half lowers the real op family ONCE (module-scoped
fixture — it is the expensive part) and asserts the shipped executables
pass every rule, match the committed manifest, and that deliberately
broken variants (dropped donation, ragged un-bucketed submits) are
flagged.  The CLI exit-code convention (0 clean / 1 findings / 2 usage)
is regression-tested for both ``tools.bamlint`` and ``tools.bamverify``.
"""
import copy
import json
import pathlib
import subprocess
import sys
import warnings

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.bamverify import ALL_RULES  # noqa: E402
from tools.bamverify.manifest import (  # noqa: E402
    MANIFEST_PATH, diff_manifest, entry_from_stats, load_manifest,
)
from tools.bamverify.rules import (  # noqa: E402
    ArtifactSpec, check_artifact, check_executable_count, check_fixture,
)

FIXTURES = REPO_ROOT / "tools" / "bamverify" / "fixtures"
BAD = sorted(p for p in (FIXTURES / "bad").iterdir() if p.is_file())
GOOD = sorted(p for p in (FIXTURES / "good").iterdir() if p.is_file())


# ------------------------------------------------------ fixtures (JAX-free)
@pytest.mark.parametrize("path", BAD, ids=lambda p: p.stem)
def test_bad_fixture_triggers_exactly_its_rule(path):
    expected, findings = check_fixture(path)
    assert expected in ALL_RULES, f"{path.name}: bad fixture expects clean?"
    assert [f.rule for f in findings] == [expected], [
        (f.rule, f.key) for f in findings
    ]


@pytest.mark.parametrize("path", GOOD, ids=lambda p: p.stem)
def test_good_fixture_is_clean(path):
    expected, findings = check_fixture(path)
    assert expected == "clean"
    assert findings == [], [f.render() for f in findings]


def test_fixture_corpus_covers_every_rule():
    covered = {check_fixture(p)[0] for p in BAD}
    assert covered == set(ALL_RULES), (
        f"rules without a bad fixture: {sorted(set(ALL_RULES) - covered)}; "
        f"fixtures for unknown rules: {sorted(covered - set(ALL_RULES))}"
    )


def test_bam505_threshold_is_exact():
    assert check_executable_count("op", 4, 4) == []
    found = check_executable_count("op", 4, 5)
    assert [f.rule for f in found] == ["BAM505"]


# ------------------------------------------------- manifest diff (JAX-free)
def test_committed_manifest_is_well_formed():
    data = json.loads(MANIFEST_PATH.read_text())
    assert data["version"] == 1
    ops = data["ops"]
    assert ops, "manifest is empty — run python -m tools.bamverify " \
                "--update-manifest"
    for key, entry in ops.items():
        assert "@" in key, key                  # op@bucket
        for field in ("scatters", "while_loops", "donation_aliases",
                      "dtypes", "instructions"):
            assert field in entry, (key, field)


def test_manifest_mutation_detected_per_op_and_bucket():
    """The CI gate: a single mutated scatter count must surface as a
    readable per-op x bucket line, not a blob."""
    recorded = load_manifest()
    key = sorted(recorded)[0]
    current = copy.deepcopy(recorded)
    current[key]["scatters"] += 3
    drift = diff_manifest(recorded, current)
    assert len(drift) == 1
    assert drift[0].startswith(f"{key}: scatters ")

    # removed and added artifacts are reported by key, too
    gone = copy.deepcopy(recorded)
    gone.pop(key)
    assert any(key in line and "no longer lowered" in line
               for line in diff_manifest(recorded, gone))
    assert any(key in line and "missing from the manifest" in line
               for line in diff_manifest(gone, recorded))


# --------------------------------------------------- live lowering (JAX)
@pytest.fixture(scope="module")
def family():
    """Lower the whole op family once (the expensive part) and share the
    artifacts across every live test."""
    from tools.bamverify.lowering import collect_stats, lower_all
    artifacts = lower_all()
    return {"artifacts": artifacts, "stats": collect_stats(artifacts)}


def test_shipped_artifacts_pass_every_rule(family):
    recorded = load_manifest()
    findings = []
    for spec, _txt in family["artifacts"]:
        findings.extend(check_artifact(
            spec, family["stats"][spec.key], recorded.get(spec.key)))
    assert findings == [], [f.render() for f in findings]


def test_repo_matches_committed_manifest(family):
    current = {key: entry_from_stats(s)
               for key, s in family["stats"].items()}
    drift = diff_manifest(load_manifest(), current)
    assert drift == [], drift


def test_shipped_donated_variants_are_aliased(family):
    donated = [(spec, family["stats"][spec.key])
               for spec, _ in family["artifacts"] if spec.donated]
    assert donated, "no donated variants lowered — registry regressed"
    for spec, stats in donated:
        assert stats.donation_aliases > 0, spec.key


def test_wait_claims_pure_all_hit_and_is_gated(family):
    waits = [(spec, family["stats"][spec.key])
             for spec, _ in family["artifacts"]
             if spec.pure_all_hit]
    assert any(spec.op.startswith("wait") for spec, _ in waits)
    assert any(spec.op.startswith("submit") for spec, _ in waits)
    for spec, stats in waits:
        # the callback exists in the executable but only behind the gate
        assert stats.custom_call_targets, spec.key
        assert stats.ungated_callbacks == [], spec.key


def test_bam501_flags_dropped_donation():
    """Donating an argument whose buffer XLA cannot reuse (output shape
    differs) silently drops the donation — exactly what BAM501 exists to
    catch at lowering time."""
    import jax
    import jax.numpy as jnp

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # "donated buffers were not usable"
        txt = (jax.jit(lambda x: jnp.concatenate([x, x]),
                       donate_argnums=(0,))
               .lower(jnp.arange(8, dtype=jnp.float32)).compile().as_text())
    spec = ArtifactSpec(op="concat", bucket=8, donated=True,
                        declared_donated=1)
    assert [f.rule for f in check_artifact(spec, txt)] == ["BAM501"]

    # and a donation that sticks is NOT flagged
    txt_ok = (jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
              .lower(jnp.arange(8, dtype=jnp.float32)).compile().as_text())
    assert check_artifact(ArtifactSpec(op="inc", bucket=8, donated=True,
                                       declared_donated=1), txt_ok) == []


def test_bam502_traced_f64_caught_even_when_optimized_out():
    """An f64 intermediate that XLA's optimizer folds away (here a
    lossless f32 -> f64 -> f32 round trip) leaves no trace in the final
    executable — the pre-optimization (jaxpr/StableHLO) side of the
    artifact must still flag the creep, because the widening is live in
    source and one refactor away from being paid for real."""
    import jax
    import jax.numpy as jnp

    def leaky(x):
        return x + x.astype(jnp.float64).astype(jnp.float32)

    with jax.enable_x64(True):
        lowered = jax.jit(leaky).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32))
        traced_f64 = "f64" in lowered.as_text()
        compiled = lowered.compile().as_text()
    assert traced_f64
    from tools.bamverify.rules import analyze_artifact
    assert "f64" not in analyze_artifact(compiled).dtypes, \
        "XLA stopped folding the round trip — pick a new dead-f64 idiom"
    spec = ArtifactSpec(op="leaky", bucket=8, traced_f64=traced_f64)
    findings = check_artifact(spec, compiled)
    assert [f.rule for f in findings] == ["BAM502"]
    # without the traced-side bit the executable alone looks clean
    assert check_artifact(ArtifactSpec(op="leaky", bucket=8),
                          compiled) == []


def test_bam505_bucketed_sweep_is_clean():
    from tools.bamverify.lowering import sweep_bucketed
    assert sweep_bucketed() == []


def test_bam505_flags_unbucketed_ragged_submits():
    """Driving submit_jit directly (no bucket padding) at more ragged
    sizes than there are buckets compiles one executable per size — the
    leak BAM505 exists to catch."""
    from repro.core.bam_array import IORequest
    from tools.bamverify.lowering import canonical_array
    import jax.numpy as jnp

    arr, st = canonical_array()
    sizes = (3, 5, 7, 11, 13)
    assert len(sizes) > len(arr.buckets)
    for n in sizes:
        idx = jnp.arange(n, dtype=jnp.int32)
        st, _tok = arr.submit_jit()(st, IORequest.read(idx, idx >= 0))
    found = check_executable_count(
        "submit", len(arr.buckets), arr.trace_counts["submit"])
    assert [f.rule for f in found] == ["BAM505"]


def test_iter_op_family_covers_the_jit_surface():
    """The registry is the verifier's ground truth: it must enumerate the
    ops, mark donatable ones, and claim purity for submit and wait (both
    gate their host callbacks)."""
    from tools.bamverify.lowering import (
        canonical_array, canonical_kv, canonical_runtime,
    )

    arr, _st = canonical_array()
    entries = {e.name: e for e in arr.iter_op_family()}
    assert set(entries) == {"read", "write", "prefetch", "submit", "wait",
                            "submit_wait", "bucketed_round"}
    assert entries["submit"].donatable and entries["wait"].donatable
    assert entries["wait"].pure_all_hit
    assert entries["submit"].pure_all_hit
    assert entries["bucketed_round"].kind == "bucketed"
    assert set(entries["bucketed_round"].trace_keys) == {"submit", "wait"}

    rt, _rst = canonical_runtime()
    rentries = {e.name: e for e in rt.iter_op_family()}
    for tenant in ("a", "b"):
        assert f"read:{tenant}" in rentries
        assert rentries[f"submit:{tenant}"].donatable
        assert rentries[f"submit:{tenant}"].pure_all_hit
        assert rentries[f"wait:{tenant}"].pure_all_hit

    kv, _kst = canonical_kv()
    kentries = {e.name: e for e in kv.iter_op_family()}
    assert set(kentries) == {"submit:kv", "wait:kv"}
    assert all(e.donatable and e.pure_all_hit for e in kentries.values())


# -------------------------------------------------------- CLI exit codes
def _cli(module, *argv):
    return subprocess.run(
        [sys.executable, "-m", module, *argv],
        cwd=REPO_ROOT, capture_output=True, text=True)


def test_bamlint_cli_exit_codes():
    assert _cli("tools.bamlint", "--list-rules").returncode == 0
    good = "tools/bamlint/fixtures/good"
    bad = "tools/bamlint/fixtures/bad/bam107.py"
    clean = _cli("tools.bamlint", f"{good}/hostsync_ok.py", "--no-baseline")
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert _cli("tools.bamlint", bad, "--no-baseline").returncode == 1
    r = _cli("tools.bamlint", "no/such/path")
    assert r.returncode == 2
    assert "no such path" in r.stderr


def test_bamverify_cli_usage_paths():
    assert _cli("tools.bamverify", "--list-rules").returncode == 0
    r = _cli("tools.bamverify", "no/such/path")
    assert r.returncode == 2
    assert "no such path" in r.stderr


def _patched_main(monkeypatch, family, argv):
    """Run the bamverify CLI in-process against the module-scoped
    artifacts (so exit-code tests don't pay a second full lowering)."""
    from tools.bamverify import __main__ as M
    from tools.bamverify import lowering as L
    monkeypatch.setattr(L, "lower_all", lambda: family["artifacts"])
    monkeypatch.setattr(L, "sweep_bucketed", lambda: [])
    return M.main(argv)


def test_bamverify_cli_clean_exit0(monkeypatch, family, capsys):
    assert _patched_main(monkeypatch, family, []) == 0
    assert "clean" in capsys.readouterr().out


def test_bamverify_cli_manifest_drift_exit1(monkeypatch, family, tmp_path,
                                            capsys):
    recorded = json.loads(MANIFEST_PATH.read_text())
    key = sorted(recorded["ops"])[0]
    recorded["ops"][key]["instructions"] += 1
    mutated = tmp_path / "manifest.json"
    mutated.write_text(json.dumps(recorded))
    rc = _patched_main(monkeypatch, family,
                       ["--manifest", str(mutated)])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"manifest drift: {key}: instructions" in out


def test_bamverify_cli_update_manifest_round_trip(monkeypatch, family,
                                                  tmp_path, capsys):
    target = tmp_path / "manifest.json"
    rc = _patched_main(monkeypatch, family,
                       ["--update-manifest", "--manifest", str(target)])
    assert rc == 0
    written = json.loads(target.read_text())["ops"]
    assert written == {key: entry_from_stats(s)
                       for key, s in family["stats"].items()}
    assert "wrote" in capsys.readouterr().out
