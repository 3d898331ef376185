"""repro-analyze: hot-path invariant linter for the serving engine.

An AST-based static-analysis pass (stdlib ``ast`` only — no third-party
lint framework) whose rules are derived from real bugs this repo fixed by
hand in earlier PRs.  Run it as::

    python -m tools.analyze src/            # report, exit 1 on unwaived findings
    python -m tools.analyze src/ --strict   # additionally fail on stale waivers

Rules
-----
KEY01   PRNG key reuse: the same key object flowing into two consumers
        without an intervening ``split``/``fold_in`` (the PR 7
        ``select_attribute`` AQR-key bug).
PAD01   Shape hazards: dynamic-shaped array constructors on hot paths that
        bypass the shared size-class helpers (retrace bombs).
SYNC01  Host-device sync on hot paths: ``.item()`` / ``float()`` / ``int()``
        / ``np.asarray`` on device-derived values inside functions reachable
        from the ``@hot_path`` roots.
CACHE01 Cache-key completeness: table-keyed caches must key on ``uid`` AND
        ``version``; signature-derived keys must exclude threshold values.
DTYPE01 64-bit literals/promotions under x64-disabled jax (the PR 1
        ``ones_like`` class).
CMP01   Comparator/tie-break totality on index-lookup paths: order-dependent
        ``max``/sorts without a deterministic tie-break key, and
        subsumption-style threshold comparisons that ignore operator
        strictness (the PR 3 ``subsumes`` ``>`` vs ``>=`` bug).

Waivers: a finding is explained away in-source with::

    offending_line()  # analyze: waive[RULE]: reason

(or the comment alone on the line directly above).  ``--strict`` also
rejects waivers that no longer match a finding, so justifications cannot
outlive the code they excuse.
"""
from tools.analyze.driver import Finding, analyze_paths, analyze_source, main

__all__ = ["Finding", "analyze_paths", "analyze_source", "main"]
