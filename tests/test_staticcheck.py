"""Tier: staticcheck — the analysis subsystem analysed.

Layer 1 (AST lint): every rule fires exactly at the tagged lines of the
seeded fixtures under tests/staticcheck_fixtures/, the negative cases in
the same files stay silent, and a full pass over src/ is finding-free
(the repo is the no-false-positives corpus).

Layer 2 (jaxpr/HLO sanitizer): the seeded bad BlockSpec trips PL201 and
PL202, a host callback trips JX101, float64 avals trip JX102, and the
donation audit distinguishes a donation XLA honors from one it silently
drops (JX103).
"""
import os
import pathlib
import subprocess
import sys

import pytest

from suite_support import LIMIT_S
from tools.staticcheck.astlint import lint_paths, lint_source
from tools.staticcheck.findings import (Finding, apply_allowlist,
                                        parse_allowlist)

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "staticcheck_fixtures"


def _hits(name):
    """(rule, line) pairs from linting one fixture file."""
    path = FIXTURES / name
    return {(f.rule, f.line)
            for f in lint_source(path.read_text(), str(path))}


def _tagged_lines(name, tag="fires here"):
    """Lines carrying the `# <RULE> fires here` marker in a fixture."""
    return {i for i, line in
            enumerate((FIXTURES / name).read_text().splitlines(), start=1)
            if tag in line}


# ---------------------------------------------------------------- layer 1

class TestFixturesFire:
    """Each seeded violation anchors at exactly its tagged line."""

    @pytest.mark.parametrize("name,rule", [
        ("key_reuse.py", "SC101"),
        ("raw_key.py", "SC102"),
        ("host_sync.py", "SC103"),
        ("f64_literal.py", "SC104"),
        ("donation.py", "SC105"),
    ])
    def test_rule_fires_at_tagged_lines_only(self, name, rule):
        hits = _hits(name)
        want = {(rule, ln) for ln in _tagged_lines(name)}
        assert want, f"fixture {name} lost its tags"
        assert hits == want, (
            f"{name}: expected exactly {sorted(want)}, got {sorted(hits)}")

    def test_negatives_documented(self):
        # every fixture carries at least one NOT-a-violation case, so the
        # exact-match assertions above double as false-positive tests
        for name in ("key_reuse.py", "raw_key.py", "host_sync.py",
                     "f64_literal.py", "donation.py"):
            assert "NOT " in (FIXTURES / name).read_text(), name


class TestOnlineHotPathRegistration:
    """The online-serving modules (serve/traffic.py, serve/parking.py)
    are registered hot paths: SC103 fires for sources linted under those
    *paths* with no pragma in the file, and the real parking module's one
    sanctioned fetch carries an allowlist justification."""

    NEW_SUFFIXES = ("src/repro/serve/traffic.py",
                    "src/repro/serve/parking.py")

    def test_suffixes_registered_in_default_config(self):
        from tools.staticcheck.astlint import DEFAULT_CONFIG
        for suffix in self.NEW_SUFFIXES:
            assert suffix in DEFAULT_CONFIG.hot_path_suffixes, suffix

    @pytest.mark.parametrize("suffix", NEW_SUFFIXES)
    def test_sc103_fires_by_path_at_tagged_lines(self, suffix):
        src = (FIXTURES / "online_hot_path.py").read_text()
        assert "staticcheck: module=" not in src  # path does the scoping
        hits = {(f.rule, f.line) for f in lint_source(src, suffix)}
        want = {("SC103", ln) for ln in _tagged_lines("online_hot_path.py")}
        assert want, "fixture lost its tags"
        assert hits == want, (
            f"{suffix}: expected exactly {sorted(want)}, got {sorted(hits)}")

    def test_same_source_is_silent_off_the_hot_path(self):
        src = (FIXTURES / "online_hot_path.py").read_text()
        assert lint_source(src, "src/repro/eval/metrics.py") == []

    def test_sc105_fires_for_parked_row_donation_misuse(self):
        # the parking restore pattern done wrong: `state` is donated into
        # the jitted restore, then read again instead of reassigned
        bad = ("import jax\n"
               "def resume(state, row):\n"
               "    restore = jax.jit(lambda s, r: s, donate_argnums=(0,))\n"
               "    new = restore(state, row)\n"
               "    return state.active\n")
        for suffix in self.NEW_SUFFIXES:
            rules = {(f.rule, f.line) for f in lint_source(bad, suffix)}
            assert ("SC105", 5) in rules, (suffix, rules)
        good = ("import jax\n"
                "def resume(state, row):\n"
                "    restore = jax.jit(lambda s, r: s, donate_argnums=(0,))\n"
                "    state = restore(state, row)\n"
                "    return state.active\n")
        assert lint_source(good, self.NEW_SUFFIXES[1]) == []

    def test_repo_parking_fetch_is_allowlisted_with_reason(self):
        src = (REPO / "src" / "repro" / "serve" / "parking.py").read_text()
        assert "staticcheck: disable=SC103" in src


class TestRouterHotPathRegistration:
    """The router-tier modules (serve/api.py, serve/router.py) are
    registered hot paths: the router's per-arrival plan loop and the
    request type's wire path must stay pure host Python, so SC103 fires
    for sources linted under those *paths* with no pragma in the file,
    and api.py's one construction-time dtype normalization carries an
    allowlist justification."""

    NEW_SUFFIXES = ("src/repro/serve/api.py",
                    "src/repro/serve/router.py")

    def test_suffixes_registered_in_default_config(self):
        from tools.staticcheck.astlint import DEFAULT_CONFIG
        for suffix in self.NEW_SUFFIXES:
            assert suffix in DEFAULT_CONFIG.hot_path_suffixes, suffix

    @pytest.mark.parametrize("suffix", NEW_SUFFIXES)
    def test_sc103_fires_by_path_at_tagged_lines(self, suffix):
        src = (FIXTURES / "router_hot_path.py").read_text()
        assert "staticcheck: module=" not in src  # path does the scoping
        hits = {(f.rule, f.line) for f in lint_source(src, suffix)}
        want = {("SC103", ln) for ln in _tagged_lines("router_hot_path.py")}
        assert want, "fixture lost its tags"
        assert hits == want, (
            f"{suffix}: expected exactly {sorted(want)}, got {sorted(hits)}")

    def test_same_source_is_silent_off_the_hot_path(self):
        src = (FIXTURES / "router_hot_path.py").read_text()
        assert lint_source(src, "src/repro/eval/metrics.py") == []

    def test_sc105_fires_for_replica_state_donation_misuse(self):
        # a routed replica done wrong: slot state donated into the jitted
        # round, then the *stale* reference read for the result harvest
        bad = ("import jax\n"
               "def round_and_harvest(state, coeffs):\n"
               "    step = jax.jit(lambda s, c: s, donate_argnums=(0,))\n"
               "    new = step(state, coeffs)\n"
               "    return state.outputs\n")
        for suffix in self.NEW_SUFFIXES:
            rules = {(f.rule, f.line) for f in lint_source(bad, suffix)}
            assert ("SC105", 5) in rules, (suffix, rules)

    def test_repo_api_normalization_is_allowlisted_with_reason(self):
        src = (REPO / "src" / "repro" / "serve" / "api.py").read_text()
        assert "staticcheck: disable=SC103" in src

    def test_repo_router_and_api_lint_clean_as_hot_paths(self):
        findings = lint_paths(
            [str(REPO / "src" / "repro" / "serve" / "api.py"),
             str(REPO / "src" / "repro" / "serve" / "router.py")])
        assert findings == [], "\n".join(f.text() for f in findings)


class TestAllowlist:
    def test_disable_with_reason_suppresses(self):
        src = ("import jax\n"
               "def f(n):\n"
               "    k = jax.random.PRNGKey(0)  "
               "# staticcheck: disable=SC102 (test helper)\n"
               "    return jax.random.normal(k, (n,))\n"
               "# staticcheck: module=library\n")
        assert lint_source(src, "x.py") == []

    def test_disable_without_reason_is_sc000(self):
        src = "x = 1  # staticcheck: disable=SC103\n"
        findings = lint_source(src, "x.py")
        assert [(f.rule, f.line) for f in findings] == [("SC000", 1)]

    def test_multiple_rules_one_comment(self):
        disabled, bad = parse_allowlist(
            "y  # staticcheck: disable=SC101,SC105 (both intended)\n", "x.py")
        assert disabled == {1: {"SC101", "SC105"}}
        assert bad == []

    def test_apply_allowlist_is_line_scoped(self):
        f1 = Finding("SC103", "x.py", 3, "m")
        f2 = Finding("SC103", "x.py", 4, "m")
        kept = apply_allowlist([f1, f2], {3: {"SC103"}})
        assert kept == [f2]

    def test_syntax_error_is_sc900(self):
        findings = lint_source("def f(:\n", "x.py")
        assert [f.rule for f in findings] == ["SC900"]


class TestRepoIsClean:
    """The no-false-positives corpus: src/ and tools/ lint clean."""

    def test_src_tree_has_no_findings(self):
        findings = lint_paths([str(REPO / "src")])
        assert findings == [], "\n".join(f.text() for f in findings)

    def test_tools_tree_has_no_findings(self):
        findings = lint_paths([str(REPO / "tools")])
        assert findings == [], "\n".join(f.text() for f in findings)

    def test_cli_exit_codes(self):
        env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
        clean = subprocess.run(
            [sys.executable, "-m", "tools.staticcheck", "src/"],
            cwd=REPO, env=env, capture_output=True, text=True)
        assert clean.returncode == 0, clean.stdout + clean.stderr
        for name in ("key_reuse.py", "raw_key.py", "host_sync.py",
                     "f64_literal.py", "donation.py"):
            seeded = subprocess.run(
                [sys.executable, "-m", "tools.staticcheck",
                 f"tests/staticcheck_fixtures/{name}"],
                cwd=REPO, env=env, capture_output=True, text=True)
            assert seeded.returncode == 1, (name, seeded.stdout)


# ---------------------------------------------------------------- layer 2

class TestSanitizer:
    def test_bad_blockspec_trips_pl201_and_pl202(self):
        from tests.staticcheck_fixtures import bad_blockspec
        from tools.staticcheck import pallas_check as plc
        closed = bad_blockspec.bad_blockspec_trace()
        eqns = plc.find_pallas_eqns(closed.jaxpr)
        assert len(eqns) == 1
        rules = {f.rule for f in plc.check_pallas_eqn(eqns[0], "fixture")}
        assert "PL201" in rules          # 32 does not divide 48
        assert "PL202" in rules          # index map walks off the array

    def test_bad_round_fused_trips_pl201_and_pl202(self):
        # the real megakernel body behind a launch that drops the
        # padding contract: ragged block + an overshooting d-tile
        from tests.staticcheck_fixtures import bad_round_fused
        from tools.staticcheck import pallas_check as plc
        closed = bad_round_fused.bad_round_fused_trace()
        eqns = plc.find_pallas_eqns(closed.jaxpr)
        assert len(eqns) == 1
        findings = plc.check_pallas_eqn(eqns[0], "fixture")
        rules = {f.rule for f in findings}
        assert "PL201" in rules          # 32 does not divide 48
        assert "PL202" in rules          # second d-tile spans [32, 64)
        # the SMEM scalar rows are exempt: only VMEM state streams flagged
        assert all("SMEM" not in f.message for f in findings
                   if f.rule in ("PL201", "PL202"))

    def test_clean_kernels_have_no_findings(self):
        from tools.staticcheck import menu
        from tools.staticcheck import pallas_check as plc
        entries = menu.kernel_entries()
        labels = [label for label, _ in entries]
        # the fused-round megakernel is a registered layer-2 entry
        assert any("round_fused/round_fused[" in l for l in labels)
        assert any("round_fused/round_fused+corr[" in l for l in labels)
        assert any("round_fused/round_predict[" in l for l in labels)
        findings = []
        for label, closed in entries:
            findings += plc.check_traced(closed.jaxpr, label)
        assert findings == [], "\n".join(f.text() for f in findings)

    def test_callback_trips_jx101(self):
        from tests.staticcheck_fixtures import bad_blockspec
        from tools.staticcheck import jaxprcheck as jxc
        closed = bad_blockspec.callback_step_trace()
        rules = {f.rule for f in jxc.check_no_callbacks(closed.jaxpr, "fx")}
        assert rules == {"JX101"}

    def test_f64_trips_jx102(self):
        import jax
        import jax.numpy as jnp
        from tools.staticcheck import jaxprcheck as jxc
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(lambda x: x * 2.0)(
                jnp.zeros((2,), jnp.float64))
        rules = {f.rule for f in jxc.check_dtypes(closed.jaxpr, "fx")}
        assert rules == {"JX102"}
        # and bf16 is fine in general mode but not under f32_only
        closed16 = jax.make_jaxpr(lambda x: x * 2)(
            jnp.zeros((2,), jnp.bfloat16))
        assert jxc.check_dtypes(closed16.jaxpr, "fx") == []
        strict = jxc.check_dtypes(closed16.jaxpr, "fx", f32_only=True)
        assert {f.rule for f in strict} == {"JX102"}

    def test_donation_audit_jx103(self):
        from tests.staticcheck_fixtures import bad_blockspec
        from tools.staticcheck import jaxprcheck as jxc
        low, comp = bad_blockspec.dropped_donation_artifacts()
        dropped = jxc.check_donation(low, comp, "fx", expect_donation=True)
        assert {f.rule for f in dropped} == {"JX103"}
        low, comp = bad_blockspec.honored_donation_artifacts()
        assert jxc.check_donation(low, comp, "fx",
                                  expect_donation=True) == []

    def test_jaxpr_hash_is_stable_and_shape_sensitive(self):
        import jax
        import jax.numpy as jnp
        from tools.staticcheck import jaxprcheck as jxc
        f = lambda x: jnp.tanh(x) + 1
        a = jxc.jaxpr_hash(jax.make_jaxpr(f)(jnp.zeros((4,))).jaxpr)
        b = jxc.jaxpr_hash(jax.make_jaxpr(f)(jnp.zeros((4,))).jaxpr)
        c = jxc.jaxpr_hash(jax.make_jaxpr(f)(jnp.zeros((8,))).jaxpr)
        assert a == b
        assert a != c
        assert len(a) == 16

    def test_hash_stability_reports_bucket_escape(self):
        from tools.staticcheck import jaxprcheck as jxc
        same = {"v": "aa"}
        assert jxc.check_hash_stability(same, {"v": "aa"}, "t") == []
        drift = jxc.check_hash_stability({"v": "aa"}, {"v": "bb"}, "t")
        assert {f.rule for f in drift} == {"JX105"}


@pytest.mark.slow
def test_quick_sanitizer_end_to_end():
    """The real serve menu, traced and sanitized: zero findings."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.staticcheck", "--sanitize", "--quick"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=LIMIT_S)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ok (0 finding(s))" in proc.stdout
