"""bamverify — lowered-artifact static analysis for the BaM hot path.

bamlint (``tools/bamlint``) lints *source*; bamverify lints what XLA
actually *emitted*.  It enumerates the jit-cached op family via the
``iter_op_family()`` registry on ``BamArray``/``BamRuntime``/
``BamKVStore``, lowers each op at canonical bucket shapes on the CPU
backend, and checks the BAM5xx rules against the compiled HLO text —
silent donation drops, dtype creep, callbacks escaping their
``lax.cond`` gate, scatter-count regressions, and shape-bucketing
executable leaks.  It then diffs a committed
**artifact manifest** (``tools/bamverify/manifest.json``: per op x bucket
-> scatter count, while-loop count, donation aliases, dtypes,
instruction count) so perf-relevant compiled-graph regressions are
caught structurally, without timing.

Run ``python -m tools.bamverify`` (CI gate) and
``python -m tools.bamverify --update-manifest`` after a deliberate
hot-path change.  See docs/static_analysis.md for the rule catalogue.

This ``__init__`` stays import-light (no JAX): ``tools/lint_docs.py``
imports ``ALL_RULES`` in jobs that never install dependencies.  Only
``tools.bamverify.lowering`` needs JAX.
"""
from __future__ import annotations

from tools.bamverify.rules import RULES as ALL_RULES

__all__ = ["ALL_RULES"]
