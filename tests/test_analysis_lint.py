"""The REP lint rules: every seeded-violation fixture fires its rule,
every clean twin passes, suppression requires a justification, and —
the CI gate itself — ``src/`` lints clean."""
import os

import pytest

from repro.analysis import lint

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC = os.path.join(os.path.dirname(HERE), "src")


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _codes(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# seeded violations fire; clean twins pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code,expected", [
    ("rep001", ["REP001"]),
    ("rep002", ["REP002"]),
    ("rep003", ["REP003"]),
    ("rep004", ["REP004"]),
    ("rep005", ["REP005", "REP005", "REP005"]),
    ("rep006", ["REP006", "REP006", "REP006"]),
    ("rep007", ["REP007", "REP007", "REP007"]),
])
def test_seeded_violation_fires(code, expected):
    findings = lint.run([_fixture(f"{code}_bad.py")])
    assert _codes(findings) == expected, [f.format() for f in findings]
    # findings carry the fixture path and a real line number
    for f in findings:
        assert f.path.endswith(f"{code}_bad.py") and f.line > 0


@pytest.mark.parametrize(
    "code",
    ["rep001", "rep002", "rep003", "rep004", "rep005", "rep006", "rep007"])
def test_clean_twin_passes(code):
    findings = lint.run([_fixture(f"{code}_clean.py")])
    assert findings == [], [f.format() for f in findings]


def test_unregistered_span_name_fires_beside_the_registry():
    # linted alone the fixture has no registry to check names against;
    # beside repro/core/spans.py its unregistered "fl.submitt" fires too
    registry = os.path.join(SRC, "repro", "core", "spans.py")
    findings = lint.run([_fixture("rep007_bad.py"), registry])
    assert _codes(findings) == ["REP007"] * 4, [f.format() for f in findings]
    assert all(f.path.endswith("rep007_bad.py") for f in findings)


def test_bare_suppression_is_rep000_and_does_not_suppress():
    findings = lint.run([_fixture("rep000_bad.py")])
    assert _codes(findings) == ["REP000", "REP003"], [
        f.format() for f in findings]


def test_justified_suppression_silences_the_rule():
    # rep003_clean.py contains a REAL violation on its last function,
    # suppressed with `# rep-noqa: REP003 -- ...`; clean-twin test above
    # already asserts zero findings — here pin that the line WOULD flag
    # without the comment (the suppression is doing work, the rule isn't
    # just blind there)
    path = _fixture("rep003_clean.py")
    with open(path, encoding="utf-8") as fh:
        text = fh.read()
    assert "rep-noqa: REP003 --" in text
    stripped = text[:text.index("  # rep-noqa")] + "\n"
    import ast
    f = lint.SourceFile(path, stripped)
    ast.parse(stripped)
    from repro.analysis.rules import RULES
    ctx = lint.ProjectContext([f])
    assert _codes(RULES["REP003"].check(f, ctx)) == ["REP003"]


# ---------------------------------------------------------------------------
# the CI gate: the repo's own source lints clean
# ---------------------------------------------------------------------------

def test_src_lints_clean():
    findings = lint.run([SRC])
    assert findings == [], "\n".join(f.format() for f in findings)


# ---------------------------------------------------------------------------
# engine/CLI behavior
# ---------------------------------------------------------------------------

def test_select_restricts_rules():
    findings = lint.run([_fixture("rep005_bad.py")], select=["REP001"])
    assert findings == []


def test_main_exit_codes(capsys):
    assert lint.main([_fixture("rep001_bad.py")]) == 1
    out = capsys.readouterr().out
    assert "REP001" in out and "rep001_bad.py" in out
    assert lint.main([_fixture("rep001_clean.py")]) == 0
    assert lint.main(["/nonexistent/thing"]) == 2


def test_project_context_registries():
    files = []
    for name in ("rep002_bad.py", "rep004_bad.py"):
        path = _fixture(name)
        with open(path, encoding="utf-8") as fh:
            files.append(lint.SourceFile(path, fh.read()))
    ctx = lint.ProjectContext(files)
    assert {"InnerConfig", "OuterSpec"} <= set(ctx.dataclasses)
    assert ctx.spec_registries[0].names == ["OuterSpec"]
    assert ctx.donators["step"].positions == (0,)


def test_conditional_donation_resolves():
    # the engine's `jit_kw = {...} if flag else {}` and inline
    # `**({"donate_argnums": ...} if ... else {})` idioms both register
    path = _fixture("rep004_clean.py")
    with open(path, encoding="utf-8") as fh:
        ctx = lint.ProjectContext([lint.SourceFile(path, fh.read())])
    assert ctx.donators["write"].positions == (0,)
