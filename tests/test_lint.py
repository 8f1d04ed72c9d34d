"""spars-lint lane: the linter catches every seeded violation class, honors
waivers, and the live tree is clean — all in tier-1, so an invariant break
(a missed trace-key field, a raw flag gate, a one-sided rule, a kernel
without its fallback) fails the default `pytest` run, not just nightly.
"""
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "..", "tools", "lint"))

import spars_lint  # noqa: E402

FIXTURES = os.path.join(_HERE, "fixtures", "lint")
# fixture trees carry only source files, never the DOCS set, so the docs
# pass (SL007) is exercised against the live tree only
CODE_RULES = [r for r in spars_lint.RULE_IDS if r != "SL007"]


def _run(root, only):
    return spars_lint.run_passes(root=root, only=only)


@pytest.mark.parametrize("rule", CODE_RULES)
def test_seeded_violation_fires(rule):
    """Each rule's fixture tree produces >=1 finding of exactly that rule."""
    root = os.path.join(FIXTURES, rule.lower())
    findings = _run(root, only=[rule])
    assert findings, f"{rule} fixture produced no findings"
    assert all(f.rule == rule for f in findings)
    assert all(f.line > 0 and f.file for f in findings)


def test_sl001_names_the_missing_field():
    findings = _run(os.path.join(FIXTURES, "sl001"), only=["SL001"])
    text = "\n".join(f.msg for f in findings)
    assert "cfg.shiny" in text and "_static_trace_key" in text


def test_sl001_catches_unkeyed_forecast_read():
    """A static `cfg.forecast_alpha` read in jitted scope (rule 10 drift
    mode: horizon/alpha must ride EngineConst, not the config) is named."""
    findings = _run(os.path.join(FIXTURES, "sl001"), only=["SL001"])
    assert any("cfg.forecast_alpha" in f.msg for f in findings)


def test_sl001_catches_unkeyed_devices_read():
    """A static `cfg.devices` read in jitted scope (§Device-sharded
    sweeps drift mode: the device count selects the compiled sharding,
    so it must be part of the sweep cache key) is named."""
    findings = _run(os.path.join(FIXTURES, "sl001"), only=["SL001"])
    assert any("cfg.devices" in f.msg for f in findings)


def test_sl002_catches_raw_forecast_gates():
    """Both rule-10 flags fire through the DEFAULT_FLAGS fallback (the
    fixture tree carries no policy.py to introspect PolicyParams from)."""
    findings = _run(os.path.join(FIXTURES, "sl002"), only=["SL002"])
    text = "\n".join(f.msg for f in findings)
    assert ".forecast_enabled" in text
    assert ".forecast_dvfs" in text


def test_sl003_catches_one_sided_forecast_twin():
    """An engine-side `apply_forecast` with no PyDES._apply_forecast is a
    one-sided rule-10 — exactly the drift SL003 keeps two-sided."""
    findings = _run(os.path.join(FIXTURES, "sl003"), only=["SL003"])
    assert any(
        "`apply_forecast`" in f.msg and "PyDES.apply_forecast" in f.msg
        for f in findings
    )


def test_sl004_flags_both_contract_halves():
    findings = _run(os.path.join(FIXTURES, "sl004"), only=["SL004"])
    text = "\n".join(f.msg for f in findings)
    assert "zero-size" in text
    assert "ref.*_reference" in text


def test_sl005_flags_host_spans_in_traced_bodies():
    """A host span, a profiler annotation or a host clock reachable from
    run_sim would freeze at trace time; a jax.named_scope is fine."""
    findings = _run(os.path.join(FIXTURES, "sl005"), only=["SL005"])
    spans_found = [f for f in findings if "host span or clock" in f.msg]
    text = "\n".join(f.msg for f in spans_found)
    assert "`time.perf_counter`" in text
    assert "`spans.span`" in text
    assert "`TraceAnnotation`" in text
    assert len(spans_found) == 3  # the named scopes are not flagged
    assert all("`process_batch`" in f.msg for f in spans_found)


def test_waiver_silences_flagged_line():
    """An `ignore[SL005,SL001]` comma-list comment above the violation
    keeps the whole waived tree clean."""
    assert _run(os.path.join(FIXTURES, "waived"), only=CODE_RULES) == []


def test_clean_fixture_is_clean():
    assert _run(os.path.join(FIXTURES, "clean"), only=CODE_RULES) == []


def test_live_tree_is_clean():
    """All seven passes (SL001-SL006 + SL007 docs) over this repo."""
    findings = spars_lint.run_passes()
    assert not findings, "\n".join(f.render() for f in findings)


def test_cli_exit_codes():
    script = os.path.join(spars_lint.REPO, "tools", "lint", "spars_lint.py")
    bad = subprocess.run(
        [sys.executable, script, "--root",
         os.path.join(FIXTURES, "sl002"), "--only", "SL002"],
        capture_output=True, text=True,
    )
    assert bad.returncode == 1
    assert "SL002" in bad.stderr
    good = subprocess.run(
        [sys.executable, script, "--root",
         os.path.join(FIXTURES, "clean"), "--only", ",".join(CODE_RULES)],
        capture_output=True, text=True,
    )
    assert good.returncode == 0, good.stderr


def test_unknown_rule_rejected():
    with pytest.raises(SystemExit):
        spars_lint.run_passes(only=["SL999"])
