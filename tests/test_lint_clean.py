"""CI gate: the package must lint clean (ISSUE 4 acceptance criterion).

``python -m bigdl_tpu.analysis.lint bigdl_tpu`` exits 0 on the merged
tree, and the grandfather allowlist stays EMPTY — any new finding either
gets fixed or carries an inline ``# lint: allow(<rule>)`` with the reason
next to the code, never a silent allowlist entry."""

import os
import subprocess
import sys

from bigdl_tpu.analysis.lint import (DEFAULT_ALLOWLIST, KNOWN_RULES,
                                     lint_paths, load_allowlist,
                                     main as lint_main)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "bigdl_tpu")


def test_package_lints_clean():
    findings = lint_paths([PKG], load_allowlist(DEFAULT_ALLOWLIST))
    assert findings == [], \
        "lint findings in bigdl_tpu/ (fix or silence inline):\n" + \
        "\n".join(str(f) for f in findings)


def test_allowlist_is_empty():
    assert load_allowlist(DEFAULT_ALLOWLIST) == set(), \
        "the lint allowlist must stay empty at merge — fix the finding " \
        "or silence it inline with '# lint: allow(<rule>)'"


def test_cli_entry_point_exits_zero():
    """The exact command the acceptance criterion names."""
    proc = subprocess.run(
        [sys.executable, "-m", "bigdl_tpu.analysis.lint", "bigdl_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_unknown_rule_is_an_error_listing_known_rules(capsys):
    """``--rule`` with an unknown name must exit nonzero and list the
    known rules — a typo'd rule name silently reporting an empty, green
    result would be a CI hole."""
    rc = lint_main(["bigdl_tpu", "--rule", "no-such-rule"])
    assert rc != 0
    err = capsys.readouterr().err
    assert "unknown rule(s): no-such-rule" in err
    for rule in KNOWN_RULES:
        assert rule in err          # the listing names every known rule


def test_known_rule_filter_exits_zero(capsys):
    rc = lint_main(["bigdl_tpu/analysis/lint.py",
                    "--rule", "undeclared-collective"])
    assert rc == 0, capsys.readouterr()
