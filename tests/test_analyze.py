"""Tests for the hot-path invariant linter (``tools.analyze``).

Three layers:

* per-rule fixtures under ``tests/analyze_fixtures/`` — every ``*_bad.py``
  must trip its rule (including the minimized PR 3 ``subsumes`` and PR 7
  key-reuse reconstructions), every ``*_good.py`` must be clean;
* the waiver machinery (line matching, reasons, staleness, strict mode);
* the self-check: ``python -m tools.analyze src/ --strict`` exits 0 on the
  repo itself — zero unexplained findings, zero stale waivers.
"""
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.analyze import analyze_paths, main  # noqa: E402
from tools.analyze.driver import analyze_source  # noqa: E402

FIXTURES = Path(__file__).parent / "analyze_fixtures"
RULES = ("KEY01", "PAD01", "SYNC01", "CACHE01", "DTYPE01", "CMP01")


def _rule_findings(fixture: str, rule: str):
    findings, _ = analyze_paths([str(FIXTURES / fixture)])
    return [f for f in findings if f.rule == rule and not f.waived]


@pytest.mark.parametrize("rule", RULES)
def test_bad_fixture_trips_rule(rule):
    found = _rule_findings(f"{rule.lower()}_bad.py", rule)
    assert found, f"{rule} did not fire on its positive fixture"


@pytest.mark.parametrize("rule", RULES)
def test_good_fixture_is_clean(rule):
    found = _rule_findings(f"{rule.lower()}_good.py", rule)
    assert not found, [f.format() for f in found]


def test_pad01_accepts_size_classes_and_still_flags_raw_sizes():
    """Sizes routed through ``row_class`` / ``group_class`` pass PAD01; a
    raw ``len(rows)`` in the same function is still flagged, and alone."""
    found = _rule_findings("pad01_size_class.py", "PAD01")
    lines = (FIXTURES / "pad01_size_class.py").read_text().splitlines()
    assert [lines[f.line - 1].strip() for f in found] == [
        "raw = jnp.ones(len(rows))  # raw row count: still one size class per len"]


def test_pr7_key_reuse_reconstruction_flagged():
    """The minimized select_attribute bug: one key into two random passes."""
    found = _rule_findings("key01_bad.py", "KEY01")
    messages = "\n".join(f.format() for f in found)
    assert "second consumer" in messages
    assert "loop" in messages and "comprehension" in messages


def test_pr3_subsumes_reconstruction_flagged():
    """Threshold comparison blind to operator strictness must trip CMP01."""
    found = _rule_findings("cmp01_bad.py", "CMP01")
    assert any("strictness" in f.message for f in found)
    assert any("tie-break" in f.message for f in found)


def test_waiver_covers_same_line_and_line_above():
    src = (
        "def aqr_cache_key(q):  # analyze: waive[CACHE01]: fixture reason\n"
        "    return (q.table,)\n"
    )
    findings = analyze_source(src)
    assert findings and all(f.waived for f in findings)
    src_above = (
        "# analyze: waive[CACHE01]: fixture reason\n"
        "def aqr_cache_key(q):\n"
        "    return (q.table,)\n"
    )
    findings = analyze_source(src_above)
    assert findings and all(f.waived for f in findings)


def test_waiver_without_reason_never_explains():
    src = (
        "def aqr_cache_key(q):\n"
        "    return (q.table,)  # analyze: waive[CACHE01]\n"
    )
    findings = analyze_source(src)
    assert findings and not any(f.waived for f in findings)


def test_waiver_for_other_rule_does_not_match():
    src = (
        "def aqr_cache_key(q):\n"
        "    return (q.table,)  # analyze: waive[KEY01]: wrong rule\n"
    )
    findings = analyze_source(src)
    assert findings and not any(f.waived for f in findings)


def test_consecutive_findings_get_their_own_waivers():
    """Same-line waivers match before line-above, so back-to-back flagged
    lines don't cascade onto each other's comments (no stale leftovers)."""
    src = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from repro.runtime.guards import hot_path\n"
        "@hot_path\n"
        "def serve(t):\n"
        "    a = np.asarray(jnp.sum(t))  # analyze: waive[SYNC01]: first\n"
        "    b = np.asarray(jnp.max(t))  # analyze: waive[SYNC01]: second\n"
        "    return a, b\n"
    )
    findings = sorted(analyze_source(src), key=lambda f: f.line)
    assert [f.waive_reason for f in findings] == ["first", "second"]


def test_to_host_is_a_sync_on_hot_paths():
    """``trace.to_host`` exists to copy a device value to the host: on a hot
    path it is flagged whatever its argument, its waiver sits on its line,
    and the names bound to its result are host values."""
    src = (
        "from repro.runtime.guards import hot_path\n"
        "from repro.runtime.trace import to_host\n"
        "@hot_path\n"
        "def serve(counts, agg):\n"
        "    c = to_host(counts)\n"
        "    a = to_host(agg)  # analyze: waive[SYNC01]: once per query\n"
        "    return float(c[0]) + float(a[0])\n"
        "def cold(counts):\n"
        "    return to_host(counts)\n"
    )
    findings = sorted(analyze_source(src), key=lambda f: f.line)
    assert [(f.rule, f.line, f.waived) for f in findings] == [
        ("SYNC01", 5, False), ("SYNC01", 6, True)]
    assert "to_host" in findings[0].message


def test_main_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("def aqr_cache_key(q):\n    return (q.table,)\n")
    assert main([str(bad)]) == 1
    good = tmp_path / "good.py"
    good.write_text("def aqr_cache_key(q, t):\n    return (t.uid, t.version)\n")
    assert main([str(good)]) == 0
    # strict: a stale waiver fails even with no findings
    stale = tmp_path / "stale.py"
    stale.write_text("x = 1  # analyze: waive[KEY01]: nothing here\n")
    assert main([str(stale)]) == 0
    assert main([str(stale), "--strict"]) == 1
    capsys.readouterr()


def test_repo_self_check_strict():
    """The merge gate: zero unexplained findings over src/, no stale waivers."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "src/", "--strict", "--quiet"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_hot_closure_does_not_leak_into_training_stack():
    """The serving hot roots must not pull models/ (training) hot through
    generic-name call edges (``stack``, ``body``, ``__init__``...)."""
    from tools.analyze.driver import Context, iter_py_files, parse_module

    mods = [parse_module(f) for f in iter_py_files([str(REPO / "src" / "repro")])]
    ctx = Context(mods)
    leaked = sorted({p for p, _ in ctx.hot
                     if "/models/" in p or "/data/" in p or "/checkpoint/" in p})
    assert not leaked, leaked
    assert any("/core/" in p for p, _ in ctx.hot)  # sanity: closure non-empty
