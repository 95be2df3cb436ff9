"""The invariant linter (repro.analysis.checks): every RPR rule against
good/bad fixture trees, suppression + baseline semantics, the CLI
contract, and the repo itself linting clean.

The three rules ported from the retired ci.yml shell guards (RPR001
print, RPR002 dispatch ladder, RPR003 Engine.run no-raise) each carry a
regression test reproducing the exact bad pattern the shell guard was
written to catch.
"""

import json
import os

import pytest

from repro.analysis.checks import (
    Baseline,
    Finding,
    make_baseline,
    run_checks,
)
from repro.analysis.checks.cli import main as cli_main
from repro.analysis.checks.findings import (
    fingerprint,
    line_annotation,
    suppressed_codes,
)

REPO_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "src", "repro",
)


def lint(tmp_path, tree, rules=None):
    """Write a fixture tree (relpath -> source) and lint it."""
    for rel, text in tree.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return run_checks([str(tmp_path)], rules=rules)


def codes(findings):
    return sorted({f.rule for f in findings if not f.baselined})


# --------------------------------------------------------------------------
# RPR001 — bare print (ported shell guard)
# --------------------------------------------------------------------------


def test_rpr001_flags_library_print(tmp_path):
    # the exact pattern the ci.yml grep guard existed for
    fs = lint(tmp_path, {
        "serving/engine2.py": "def f():\n    print('debug')\n",
    }, rules=["RPR001"])
    assert [f.rule for f in fs] == ["RPR001"]
    assert fs[0].line == 2


def test_rpr001_exempts_clis_and_validator(tmp_path):
    fs = lint(tmp_path, {
        "launch/serve2.py": "print('user-facing')\n",
        "analysis/tool.py": "print('cli')\n",
        "obs/validate.py": "print('validator')\n",
    }, rules=["RPR001"])
    assert fs == []


def test_rpr001_allows_log_alias(tmp_path):
    # `log = print` (a reference, not a call) stays legal, as under the
    # old grep exclusion
    fs = lint(tmp_path, {
        "serving/x.py": "def f(log=print):\n    log('ok')\n",
    }, rules=["RPR001"])
    assert fs == []


# --------------------------------------------------------------------------
# RPR002 — dispatch ladders (ported shell guard)
# --------------------------------------------------------------------------


def test_rpr002_flags_ladder_outside_registry(tmp_path):
    fs = lint(tmp_path, {
        "models/other.py":
            "def f(kind, variant):\n"
            "    if kind == 'gla':\n"
            "        return 1\n"
            "    if variant != 'hla2':\n"
            "        return 2\n",
    }, rules=["RPR002"])
    assert [f.line for f in fs] == [2, 4]


def test_rpr002_registry_and_attributes_allowed(tmp_path):
    fs = lint(tmp_path, {
        # seq_op.py is the one sanctioned dispatch site
        "models/seq_op.py": "def f(kind):\n    return kind == 'gla'\n",
        # attribute access is config metadata, not dispatch
        "launch/go.py": "def f(c):\n    return c.kind == 'train'\n",
        # right-operand comparisons (filter style) stay legal
        "obs/trace.py":
            "def f(es, kind):\n"
            "    return [e for e in es if e['kind'] == kind]\n",
    }, rules=["RPR002"])
    assert fs == []


# --------------------------------------------------------------------------
# RPR003 — Engine.run no-raise (ported shell guard)
# --------------------------------------------------------------------------

_ENGINE_BAD = """\
class Engine:
    def run(self):
        while self.pending:
            raise RuntimeError('boom')
"""

_ENGINE_GOOD = """\
class Engine:
    def run(self):
        if not self.ready:
            raise RuntimeError('before the loop is fine')
        while self.pending:
            self.step()
"""


def test_rpr003_flags_raise_in_drive_loop(tmp_path):
    fs = lint(tmp_path, {"serving/engine.py": _ENGINE_BAD},
              rules=["RPR003"])
    assert [f.rule for f in fs] == ["RPR003"]
    assert fs[0].line == 4


def test_rpr003_raise_outside_loop_ok(tmp_path):
    fs = lint(tmp_path, {"serving/engine.py": _ENGINE_GOOD},
              rules=["RPR003"])
    assert fs == []


def test_rpr003_missing_anchor_is_a_finding(tmp_path):
    # if Engine.run is renamed away, the contract must fail loudly, not
    # silently stop checking
    fs = lint(tmp_path, {"serving/engine.py": "class Other:\n    pass\n"},
              rules=["RPR003"])
    assert [f.rule for f in fs] == ["RPR003"]
    assert "not found" in fs[0].message


# --------------------------------------------------------------------------
# RPR004 — host-sync discipline
# --------------------------------------------------------------------------


def test_rpr004_unannotated_device_get(tmp_path):
    fs = lint(tmp_path, {
        "serving/x.py":
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)\n",
    }, rules=["RPR004"])
    assert [f.rule for f in fs] == ["RPR004"]


def test_rpr004_sync_point_annotation_clears(tmp_path):
    fs = lint(tmp_path, {
        "serving/x.py":
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)  # sync-point: block endpoint\n",
    }, rules=["RPR004"])
    assert fs == []


def test_rpr004_annotation_needs_a_reason(tmp_path):
    fs = lint(tmp_path, {
        "serving/x.py":
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)  # sync-point:\n",
    }, rules=["RPR004"])
    assert [f.rule for f in fs] == ["RPR004"]


def test_rpr004_cast_of_device_value(tmp_path):
    fs = lint(tmp_path, {
        "serving/x.py":
            "import jax.numpy as jnp\n"
            "def f(a, b):\n"
            "    v = jnp.dot(a, b)\n"
            "    return int(v)\n",
        "models/y.py":
            "import jax.numpy as jnp\n"
            "def g(s):\n"
            "    return s.item()\n",
    }, rules=["RPR004"])
    assert [(f.path, f.rule) for f in fs] == [
        ("models/y.py", "RPR004"), ("serving/x.py", "RPR004"),
    ]


def test_rpr004_host_values_and_other_dirs_unflagged(tmp_path):
    fs = lint(tmp_path, {
        # the sanctioned pattern: one device_get, casts on the host copy
        "serving/ok.py":
            "import jax\n"
            "import numpy as np\n"
            "def f(v):\n"
            "    h = jax.device_get(v)  # sync-point: block endpoint\n"
            "    h = np.asarray(h)\n"
            "    return int(h[0])\n",
        # runtime/ is not a hot path — no findings there
        "runtime/loop.py":
            "import jax\n"
            "def g(x):\n"
            "    return jax.device_get(x)\n",
    }, rules=["RPR004"])
    assert fs == []


def test_rpr004_taint_is_function_scoped(tmp_path):
    # `key = jax.random...` in one method must not poison the name `key`
    # in a sibling method that only handles host values
    fs = lint(tmp_path, {
        "serving/x.py":
            "import jax\n"
            "class C:\n"
            "    def a(self):\n"
            "        key = jax.random.PRNGKey(0)\n"
            "        self.key = key\n"
            "    def b(self, key):\n"
            "        return int(key)\n",
    }, rules=["RPR004"])
    assert fs == []


# --------------------------------------------------------------------------
# RPR005 — jit purity
# --------------------------------------------------------------------------


def test_rpr005_time_in_jitted_fn(tmp_path):
    fs = lint(tmp_path, {
        "runtime/x.py":
            "import jax, time\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    t = time.time()\n"
            "    return x + t\n",
    }, rules=["RPR005"])
    assert [f.rule for f in fs] == ["RPR005"]
    assert "time.time" in fs[0].message


def test_rpr005_np_random_in_scan_body(tmp_path):
    # traced by reference: body is passed by name to lax.scan
    fs = lint(tmp_path, {
        "models/x.py":
            "import jax, numpy as np\n"
            "def body(c, x):\n"
            "    return c, x + np.random.randn()\n"
            "def f(xs):\n"
            "    return jax.lax.scan(body, 0.0, xs)\n",
    }, rules=["RPR005"])
    assert [f.rule for f in fs] == ["RPR005"]


def test_rpr005_host_time_and_jax_random_ok(tmp_path):
    fs = lint(tmp_path, {
        "runtime/x.py":
            "import jax, time\n"
            "@jax.jit\n"
            "def f(x, key):\n"
            "    return x + jax.random.uniform(key)\n"
            "def loop(x, key):\n"
            "    t0 = time.time()\n"  # host-side timing is fine
            "    return f(x, key), time.time() - t0\n",
    }, rules=["RPR005"])
    assert fs == []


def test_rpr005_nested_def_inside_jitted_fn(tmp_path):
    fs = lint(tmp_path, {
        "models/x.py":
            "import jax, random\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    def inner(y):\n"
            "        return y * random.random()\n"
            "    return inner(x)\n",
    }, rules=["RPR005"])
    assert [f.rule for f in fs] == ["RPR005"]


# --------------------------------------------------------------------------
# RPR006 — fault-point cross-check
# --------------------------------------------------------------------------

_CATALOG = (
    "from typing import Dict\n"
    "FAULT_POINTS: Dict[str, str] = {\n"
    "    'engine.boom': 'a fired point',\n"
    "    'dead.point': 'never fired anywhere',\n"
    "}\n"
)


def test_rpr006_dead_entry_and_unregistered_site(tmp_path):
    fs = lint(tmp_path, {
        "runtime/faults.py": _CATALOG,
        "serving/engine.py":
            "def f(plan):\n"
            "    plan.raise_if('engine.boom')\n"
            "    plan.hit('typo.point')\n",
    }, rules=["RPR006"])
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 2
    assert "dead.point" in msgs[0] and "no live firing site" in msgs[0]
    assert "typo.point" in msgs[1] and "unregistered" in msgs[1]


def test_rpr006_clean_when_catalog_matches(tmp_path):
    fs = lint(tmp_path, {
        "runtime/faults.py":
            "FAULT_POINTS = {'engine.boom': 'doc'}\n",
        "serving/engine.py":
            "def f(self):\n"
            "    self._raise_fault('engine.boom')\n",
    }, rules=["RPR006"])
    assert fs == []


def test_rpr006_skips_trees_without_catalog(tmp_path):
    # linting a subtree (no runtime/faults.py) must not spray findings
    fs = lint(tmp_path, {
        "serving/engine.py": "def f(p):\n    p.hit('whatever.point')\n",
    }, rules=["RPR006"])
    assert fs == []


# --------------------------------------------------------------------------
# RPR007 — obs naming schema
# --------------------------------------------------------------------------


def test_rpr007_metric_shapes(tmp_path):
    fs = lint(tmp_path, {
        "serving/m.py":
            "def f(m):\n"
            "    m.counter('serving_requests', 'h')\n"       # no _total
            "    m.gauge('serving_queue_total', 'h')\n"      # _total on gauge
            "    m.histogram('serving_ttft', 'h')\n"         # no unit
            "    m.histogram('BadName', 'h')\n"              # not snake
            "    m.event('FooBar')\n"                        # not dotted
            "    m.anchor('ProfileStart')\n",                # not dotted
    }, rules=["RPR007"])
    assert [f.line for f in fs] == [2, 3, 4, 5, 6, 7]


def test_rpr007_bench_row_names(tmp_path):
    """bench history rows must be slash-separated snake_case paths."""
    fs = lint(tmp_path, {
        "benchmarks/b.py":
            "def f(h):\n"
            "    h.bench_row('ops/gla/decode_tok_per_s', 1.0, unit='x')\n"
            "    h.bench_row('kernels/hla2_fwd', 1.0, unit='x')\n"
            "    h.bench_row('BadName/row', 1.0, unit='x')\n"   # not snake
            "    h.bench_row('single_segment', 1.0, unit='x')\n"  # no slash
            "    h.bench_row('ops//empty', 1.0, unit='x')\n",     # empty seg
    }, rules=["RPR007"])
    assert [f.line for f in fs] == [4, 5, 6]


def test_rpr007_schema_conformant_names_pass(tmp_path):
    fs = lint(tmp_path, {
        "serving/m.py":
            "def f(m, obs):\n"
            "    m.counter('serving_requests_total', 'h')\n"
            "    m.gauge('serving_queue_depth', 'h')\n"
            "    m.histogram('serving_ttft_seconds', 'h')\n"
            "    obs.event('request.first_token')\n"
            "    obs.span('engine.decode_block')\n"
            "    obs.timer('engine.spec_round')\n"
            "    obs.anchor('profile.start')\n",
    }, rules=["RPR007"])
    assert fs == []


# --------------------------------------------------------------------------
# suppressions, annotations, baseline
# --------------------------------------------------------------------------


def test_noqa_suppresses_named_code_only(tmp_path):
    fs = lint(tmp_path, {
        "serving/x.py":
            "def f():\n"
            "    print('one')  # noqa: RPR001\n"
            "    print('two')  # noqa: RPR002\n"  # wrong code: still flagged
            "    print('three')  # noqa\n",       # bare noqa: not honored
    }, rules=["RPR001"])
    assert [f.line for f in fs] == [3, 4]


def test_suppressed_codes_parsing():
    assert suppressed_codes("x = 1  # noqa: RPR001") == ["RPR001"]
    assert suppressed_codes("x  # noqa: RPR001, RPR004") == \
        ["RPR001", "RPR004"]
    assert suppressed_codes("x  # noqa") == []
    assert line_annotation("y  # sync-point: ttft endpoint",
                           "sync-point") == "ttft endpoint"
    assert line_annotation("y  # sync-point:", "sync-point") is None


def test_baseline_accepts_old_findings_not_new(tmp_path):
    tree = {"serving/x.py": "def f():\n    print('legacy')\n"}
    for rel, text in tree.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True)
        p.write_text(text)
    bl = make_baseline([str(tmp_path)], rules=["RPR001"])
    assert len(bl.fingerprints) == 1

    # baselined finding: reported, stamped, does not count as new
    fs = run_checks([str(tmp_path)], rules=["RPR001"], baseline=bl)
    assert len(fs) == 1 and fs[0].baselined

    # a NEW copy of the same pattern is a new finding
    (tmp_path / "serving" / "x.py").write_text(
        "def f():\n    print('legacy')\n    print('new')\n"
    )
    fs = run_checks([str(tmp_path)], rules=["RPR001"], baseline=bl)
    assert [f.baselined for f in sorted(fs, key=lambda f: f.line)] == \
        [True, False]


def test_baseline_survives_line_renumbering(tmp_path):
    p = tmp_path / "serving" / "x.py"
    p.parent.mkdir(parents=True)
    p.write_text("def f():\n    print('legacy')\n")
    bl = make_baseline([str(tmp_path)], rules=["RPR001"])
    # shift the finding down two lines: content-hash fingerprint holds
    p.write_text("import os\n\ndef f():\n    print('legacy')\n")
    fs = run_checks([str(tmp_path)], rules=["RPR001"], baseline=bl)
    assert len(fs) == 1 and fs[0].baselined


def test_baseline_roundtrip(tmp_path):
    bl = Baseline(["aaaa", "bbbb"])
    path = str(tmp_path / "baseline.json")
    bl.save(path)
    loaded = Baseline.load(path)
    assert loaded.fingerprints == {"aaaa", "bbbb"}
    with pytest.raises(ValueError):
        (tmp_path / "bad.json").write_text('{"schema": "nope"}')
        Baseline.load(str(tmp_path / "bad.json"))


def test_fingerprint_distinguishes_occurrences():
    lines = ["print('x')", "print('x')"]
    a = fingerprint(Finding("RPR001", "p.py", 1, 0, "m"), lines)
    b = fingerprint(Finding("RPR001", "p.py", 2, 0, "m"), lines)
    assert a != b


# --------------------------------------------------------------------------
# CLI contract
# --------------------------------------------------------------------------


def _write_bad_tree(tmp_path):
    p = tmp_path / "serving" / "x.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text("def f():\n    print('nope')\n")


def test_cli_exit_codes_and_json(tmp_path, capsys):
    _write_bad_tree(tmp_path)
    rc = cli_main([str(tmp_path), "--rules", "RPR001", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["schema"] == "repro.checks.findings/v1"
    assert out["counts"] == {"RPR001": 1}
    assert out["findings"][0]["path"] == "serving/x.py"


def test_cli_baseline_workflow(tmp_path, capsys):
    _write_bad_tree(tmp_path)
    bl_path = str(tmp_path / "baseline.json")
    assert cli_main([str(tmp_path), "--rules", "RPR001",
                     "--write-baseline", bl_path]) == 0
    capsys.readouterr()
    # same tree + baseline: clean exit, finding reported as baselined
    rc = cli_main([str(tmp_path), "--rules", "RPR001",
                   "--baseline", bl_path])
    out = capsys.readouterr().out
    assert rc == 0 and "(baselined)" in out and "0 new findings" in out


def test_cli_unknown_rule_is_usage_error(tmp_path):
    assert cli_main([str(tmp_path), "--rules", "RPR999"]) == 2


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in ("RPR001", "RPR002", "RPR003", "RPR004", "RPR005",
                 "RPR006", "RPR007"):
        assert code in out


def test_syntax_error_becomes_finding(tmp_path):
    (tmp_path / "bad.py").write_text("def f(:\n")
    fs = run_checks([str(tmp_path)])
    assert [f.rule for f in fs] == ["RPR000"]


# --------------------------------------------------------------------------
# the repo itself
# --------------------------------------------------------------------------


def test_repo_lints_clean():
    """The acceptance bar: zero unbaselined findings on src/repro.  Every
    invariant the retired shell guards enforced (and the four new rules)
    holds on the real tree."""
    fs = run_checks([REPO_SRC])
    assert [f.render() for f in fs if not f.baselined] == []
