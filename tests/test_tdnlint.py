"""tdnlint acceptance: each rule fires on its violating fixture with
the right id and line, stays silent on the clean twin, and the `tdn
lint` gate holds in both directions — exit 0 on the shipped tree
(zero non-baselined findings), exit 1 on a planted violation. Also
covers the suppression and baseline workflows (docs/STATIC_ANALYSIS.md)."""

import json
import os
import shutil

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "lint_fixtures")


def _load_tdnlint():
    # One loading contract for the whole repo: the CLI's by-path loader
    # (tests exercising it here keeps it from drifting).
    from tpu_dist_nn.cli import _load_tdnlint as load

    return load()


def _marker_lines(path):
    """Expected finding lines = the fixture's `# <- violation` markers,
    so editing a fixture cannot desynchronize the assertions."""
    with open(path) as f:
        return sorted(
            i for i, ln in enumerate(f, start=1) if "# <- violation" in ln
        )


RULE_FIXTURES = [
    ("lock-discipline", "lock_discipline"),
    ("tick-purity", "tick_purity"),
    ("metric-series-lifecycle", "metric_lifecycle"),
    ("admin-actuation", "admin_actuation"),
    ("jit-purity", "jit_purity"),
    # ISSUE 14 twins: the goodput tick callback rides the sampler via
    # the NEW add_goodput verb (tick-purity must cover it), and its
    # closed-label-space families carry no lifecycle obligation while
    # a per-replica fleet exporter does.
    ("tick-purity", "goodput_tick"),
    ("metric-series-lifecycle", "goodput_metrics"),
]


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_fires_on_violating_fixture(rule, stem):
    tdnlint = _load_tdnlint()
    bad = os.path.join(FIXTURES, f"{stem}_bad.py")
    result = tdnlint.run_lint([bad])
    assert result["new"], f"{rule} found nothing in {bad}"
    assert {f.rule for f in result["new"]} == {rule}
    assert sorted(f.line for f in result["new"]) == _marker_lines(bad)


@pytest.mark.parametrize("rule,stem", RULE_FIXTURES)
def test_rule_silent_on_clean_twin(rule, stem):
    tdnlint = _load_tdnlint()
    clean = os.path.join(FIXTURES, f"{stem}_clean.py")
    result = tdnlint.run_lint([clean])
    assert result["new"] == [], [f.render() for f in result["new"]]


def test_shipped_tree_is_clean_via_tdn_lint_cli(capsys):
    """The acceptance gate's zero direction: `tdn lint tpu_dist_nn/`
    exits 0 with zero non-baselined findings on the shipped tree."""
    from tpu_dist_nn.cli import main

    rc = main(["lint", os.path.join(REPO_ROOT, "tpu_dist_nn")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 findings" in out


def test_tdn_lint_exits_nonzero_on_planted_violation(tmp_path, capsys):
    """The other direction: a planted violation fails the gate with
    the offending rule id in the report."""
    planted = tmp_path / "planted.py"
    shutil.copyfile(
        os.path.join(FIXTURES, "lock_discipline_bad.py"), planted
    )
    from tpu_dist_nn.cli import main

    rc = main(["lint", str(planted), "--baseline", ""])
    out = capsys.readouterr().out
    assert rc == 1
    assert "[lock-discipline]" in out


def test_inline_suppression_silences_one_line(tmp_path):
    tdnlint = _load_tdnlint()
    src = open(
        os.path.join(FIXTURES, "lock_discipline_bad.py")
    ).read().replace(
        "# <- violation", "# tdnlint: disable=lock-discipline"
    )
    planted = tmp_path / "suppressed.py"
    planted.write_text(src)
    result = tdnlint.run_lint([str(planted)])
    assert result["new"] == []
    assert result["suppressed_total"] == 1


def test_baseline_workflow_grandfathers_then_reports_stale(tmp_path,
                                                          capsys):
    """--update-baseline grandfathers current findings (TODO
    justification), the next run exits 0 against it, and an entry whose
    finding was fixed is reported stale instead of rotting silently."""
    tdnlint = _load_tdnlint()
    planted = tmp_path / "mod.py"
    shutil.copyfile(
        os.path.join(FIXTURES, "lock_discipline_bad.py"), planted
    )
    base = tmp_path / "baseline.json"
    rc = tdnlint.main([str(planted), "--baseline", str(base),
                       "--update-baseline"])
    assert rc == 0
    doc = json.loads(base.read_text())
    assert len(doc["findings"]) == 1
    assert "TODO" in doc["findings"][0]["justification"]
    rc = tdnlint.main([str(planted), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "1 baselined" in out
    # Fix the violation: the entry goes stale (stderr warning, exit 0).
    shutil.copyfile(
        os.path.join(FIXTURES, "lock_discipline_clean.py"), planted
    )
    rc = tdnlint.main([str(planted), "--baseline", str(base)])
    captured = capsys.readouterr()
    assert rc == 0
    assert "stale baseline entry" in captured.err


def test_baseline_fingerprints_survive_line_drift(tmp_path):
    """Fingerprints are line-number-free: unrelated edits above a
    grandfathered finding must not invalidate its baseline entry."""
    tdnlint = _load_tdnlint()
    planted = tmp_path / "mod.py"
    src = open(os.path.join(FIXTURES, "lock_discipline_bad.py")).read()
    planted.write_text(src)
    base = tmp_path / "baseline.json"
    assert tdnlint.main([str(planted), "--baseline", str(base),
                         "--update-baseline"]) == 0
    planted.write_text("# an unrelated comment pushing lines down\n"
                       "# and another one\n" + src)
    result = tdnlint.run_lint([str(planted)],
                              baseline_path=str(base))
    assert result["new"] == []
    assert len(result["baselined"]) == 1


def test_list_rules_names_all_five(capsys):
    from tpu_dist_nn.cli import main

    assert main(["lint", "--list-rules"]) == 0
    out = capsys.readouterr().out.split()
    assert out == ["lock-discipline", "tick-purity",
                   "metric-series-lifecycle", "admin-actuation",
                   "jit-purity"]


def test_lint_json_line_is_machine_readable(tmp_path, capsys):
    from tpu_dist_nn.cli import main

    planted = tmp_path / "planted.py"
    shutil.copyfile(
        os.path.join(FIXTURES, "metric_lifecycle_bad.py"), planted
    )
    rc = main(["lint", str(planted), "--baseline", "", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out.strip().splitlines()[-1])
    assert doc["findings"][0]["rule"] == "metric-series-lifecycle"
    assert doc["findings"][0]["line"] == _marker_lines(planted)[0]


