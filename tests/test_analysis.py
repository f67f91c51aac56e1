"""Tests for ``repro.analysis`` — the repo's static-analysis pass.

Each rule gets a minimal bad-code fixture proving it fires, plus
suppression-marker semantics, import-graph behaviour (transitive
chains, lazy imports, cycles), doc-table drift, and the CLI's
non-zero-exit contracts (findings, parse errors, typo'd suppressions).
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis import all_rules, make_config, run_analysis
from repro.analysis import imports as imports_lib
from repro.analysis.core import Project, parse_suppressions
from repro.analysis.docsync import WireSpecDrift, parse_obs_table
from repro.analysis.rules import (ClockDiscipline, DeadlineDiscipline,
                                  DeterministicIteration, JaxImportHygiene,
                                  LockDiscipline, NoPickleOnWire)
from repro.analysis.tracecheck import check_trace

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# fixture-tree helpers
# ---------------------------------------------------------------------------

def write_tree(root: Path, files) -> Path:
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text), encoding="utf-8")
    return root

def lint(root: Path, overrides, rules=None):
    return run_analysis(root, config=overrides, rules=rules)

def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# jax-import-hygiene
# ---------------------------------------------------------------------------

JAX_CFG = {
    "jax_free_modules": ["pkg.leaf"],
    "pickle_scope": [], "random_scope": [], "ordered_replay_modules": [],
    "pure_sim_modules": [], "wall_clock_allowed": [], "lock_modules": [],
}

def test_jax_hygiene_direct_import_fires(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/leaf.py": "import jax\n",
    })
    fs = lint(tmp_path, JAX_CFG, rules=[JaxImportHygiene()])
    assert len(fs) == 1
    assert fs[0].rule == "jax-import-hygiene"
    assert fs[0].path == "src/pkg/leaf.py"

def test_jax_hygiene_transitive_chain_reported(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/leaf.py": "from pkg import mid\n",
        "src/pkg/mid.py": "import pkg.heavy\n",
        "src/pkg/heavy.py": "import jax.numpy\n",
    })
    fs = lint(tmp_path, JAX_CFG, rules=[JaxImportHygiene()])
    assert len(fs) == 1
    assert "pkg.leaf -> pkg.mid -> pkg.heavy" in fs[0].message

def test_jax_hygiene_lazy_import_is_clean(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/leaf.py": """\
            def f():
                import jax
                return jax
        """,
    })
    assert lint(tmp_path, JAX_CFG, rules=[JaxImportHygiene()]) == []

def test_jax_hygiene_eager_package_init_taints_leaf(tmp_path):
    # importing pkg.leaf runs pkg/__init__ first — the classic trap
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "from pkg import heavy\n",
        "src/pkg/heavy.py": "import jax\n",
        "src/pkg/leaf.py": "x = 1\n",
    })
    fs = lint(tmp_path, JAX_CFG, rules=[JaxImportHygiene()])
    assert len(fs) == 1 and "via pkg.leaf -> pkg" in fs[0].message

def test_import_graph_cycle_terminates(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/a.py": "from pkg import b\n",
        "src/pkg/b.py": "from pkg import a\n",
    })
    proj = Project.load(tmp_path, make_config(JAX_CFG))
    mods = imports_lib.build_graph(proj)
    assert imports_lib.find_taint_chain("pkg.a", mods, ["jax"]) is None

def test_type_checking_imports_ignored(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/leaf.py": """\
            from typing import TYPE_CHECKING
            if TYPE_CHECKING:
                import jax
        """,
    })
    assert lint(tmp_path, JAX_CFG, rules=[JaxImportHygiene()]) == []


# ---------------------------------------------------------------------------
# no-pickle-on-wire
# ---------------------------------------------------------------------------

PICKLE_CFG = dict(JAX_CFG, jax_free_modules=[], pickle_scope=["src"])

def test_pickle_import_and_call_fire(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": """\
            import pickle
            def f(x):
                return pickle.dumps(x)
        """,
    })
    fs = lint(tmp_path, PICKLE_CFG, rules=[NoPickleOnWire()])
    assert [f.line for f in fs] == [1, 3]
    assert rules_of(fs) == ["no-pickle-on-wire"]

def test_pickle_marker_with_reason_suppresses(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": """\
            import pickle  # repro-lint: allow[no-pickle-on-wire] spawn bootstrap only
            def f(x):
                # repro-lint: allow[no-pickle-on-wire] trusted local blob
                return pickle.dumps(x)
        """,
    })
    assert lint(tmp_path, PICKLE_CFG, rules=[NoPickleOnWire()]) == []

def test_pickle_marker_without_reason_is_bad_suppression(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py":
            "import pickle  # repro-lint: allow[no-pickle-on-wire]\n",
    })
    fs = lint(tmp_path, PICKLE_CFG, rules=[NoPickleOnWire()])
    assert rules_of(fs) == ["bad-suppression"]
    assert "reason" in fs[0].message

def test_marker_in_string_literal_is_not_a_suppression():
    sups = parse_suppressions(
        's = "# repro-lint: allow[no-pickle-on-wire] nope"\n'
        "x = 1  # repro-lint: allow[no-pickle-on-wire] real one\n")
    assert len(sups) == 1 and sups[0].line == 2


# ---------------------------------------------------------------------------
# clock-discipline
# ---------------------------------------------------------------------------

CLOCK_CFG = dict(JAX_CFG, jax_free_modules=[],
                 wall_clock_scope=["src"],
                 wall_clock_allowed=["src/pkg/telemetry.py"],
                 pure_sim_modules=["src/pkg/numerics.py"])

def test_wall_clock_fires_outside_allowlist(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": """\
            import time, datetime
            t = time.time()
            d = datetime.datetime.now()
        """,
        "src/pkg/telemetry.py": """\
            import time
            pair = (time.monotonic_ns(), time.time_ns())
        """,
    })
    fs = lint(tmp_path, CLOCK_CFG, rules=[ClockDiscipline()])
    assert [(f.path, f.line) for f in fs] == [
        ("src/pkg/m.py", 2), ("src/pkg/m.py", 3)]

def test_monotonic_banned_in_pure_sim_modules(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/numerics.py": "import time\nt = time.monotonic()\n",
        "src/pkg/m.py": "import time\nt = time.monotonic()\n",  # fine here
    })
    fs = lint(tmp_path, CLOCK_CFG, rules=[ClockDiscipline()])
    assert [(f.path, f.line) for f in fs] == [("src/pkg/numerics.py", 2)]

def test_from_time_import_flagged(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": "from time import time\n",
    })
    fs = lint(tmp_path, CLOCK_CFG, rules=[ClockDiscipline()])
    assert len(fs) == 1 and "qualified" in fs[0].message


# ---------------------------------------------------------------------------
# deterministic-iteration
# ---------------------------------------------------------------------------

DET_CFG = dict(JAX_CFG, jax_free_modules=[],
               ordered_replay_modules=["src/pkg/replay.py"],
               random_scope=["src"])

def test_unsorted_dict_iteration_fires(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/replay.py": """\
            def f(d):
                out = []
                for k, v in d.items():
                    out.append((k, v))
                return out
        """,
    })
    fs = lint(tmp_path, DET_CFG, rules=[DeterministicIteration()])
    assert len(fs) == 1 and fs[0].line == 3

def test_sorted_dict_iteration_is_clean(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/replay.py": """\
            def f(d):
                return [v for _, v in sorted(d.items())]
        """,
    })
    assert lint(tmp_path, DET_CFG, rules=[DeterministicIteration()]) == []

def test_set_literal_iteration_fires(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/replay.py": """\
            def f():
                for x in {3, 1, 2}:
                    print(x)
        """,
    })
    fs = lint(tmp_path, DET_CFG, rules=[DeterministicIteration()])
    assert len(fs) == 1 and "set" in fs[0].message

def test_order_free_reducer_over_items_is_clean(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/replay.py": """\
            def f(d):
                return max(v for k, v in d.items())
        """,
    })
    assert lint(tmp_path, DET_CFG, rules=[DeterministicIteration()]) == []

def test_stdlib_random_banned_everywhere_in_scope(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/anywhere.py": "import random\n",
    })
    fs = lint(tmp_path, DET_CFG, rules=[DeterministicIteration()])
    assert len(fs) == 1 and "stdlib random" in fs[0].message

def test_legacy_np_random_banned(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/anywhere.py": """\
            import numpy as np
            x = np.random.randn(3)
            g = np.random.default_rng(0)   # the sanctioned API
        """,
    })
    fs = lint(tmp_path, DET_CFG, rules=[DeterministicIteration()])
    assert len(fs) == 1 and fs[0].line == 2


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

LOCK_CFG = dict(JAX_CFG, jax_free_modules=[],
                lock_modules=["src/pkg/a.py", "src/pkg/b.py"])

def test_bare_acquire_release_fire(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/a.py": """\
            import threading
            lock = threading.Lock()
            def f():
                lock.acquire()
                lock.release()
        """,
    })
    fs = lint(tmp_path, LOCK_CFG, rules=[LockDiscipline()])
    assert [f.line for f in fs] == [4, 5]

def test_lock_order_cycle_across_files_fires(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/a.py": """\
            import pkg.b as b
            class S:
                def f(self):
                    with b.x_lock:
                        with b.y_lock:
                            pass
        """,
        "src/pkg/b.py": """\
            import threading
            x_lock = threading.Lock()
            y_lock = threading.Lock()
            def g():
                with y_lock:
                    with x_lock:
                        pass
        """,
    })
    fs = lint(tmp_path, LOCK_CFG, rules=[LockDiscipline()])
    assert len(fs) == 1 and "lock-ordering cycle" in fs[0].message

def test_consistent_nesting_is_clean(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/a.py": """\
            import threading
            x_lock = threading.Lock()
            y_lock = threading.Lock()
            def f():
                with x_lock:
                    with y_lock:
                        pass
            def g():
                with x_lock:
                    with y_lock:
                        pass
        """,
    })
    assert lint(tmp_path, LOCK_CFG, rules=[LockDiscipline()]) == []


# ---------------------------------------------------------------------------
# deadline-discipline
# ---------------------------------------------------------------------------

DEADLINE_CFG = dict(JAX_CFG, jax_free_modules=[],
                    deadline_modules=["src/pkg/w.py"])

def test_bare_blocking_waits_fire(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/w.py": """\
            def f(q, conn, th, cond):
                q.get()
                conn.recv()
                th.join()
                cond.wait()
        """,
    })
    fs = lint(tmp_path, DEADLINE_CFG, rules=[DeadlineDiscipline()])
    assert rules_of(fs) == ["deadline-discipline"]
    assert [f.line for f in fs] == [2, 3, 4, 5]

def test_deadlined_and_marked_waits_are_clean(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/w.py": """\
            def f(q, th, cond, block):
                q.get(timeout=1.0)
                q.get(True, 1.0)
                th.join(5.0)
                cond.wait(timeout=0.5)
                # repro-lint: allow[deadline-discipline] producer posts a
                # terminator from its finally: block
                block.recv()
        """,
        "src/pkg/other.py": "def g(q):\n    q.get()\n",  # out of scope
    })
    assert lint(tmp_path, DEADLINE_CFG,
                rules=[DeadlineDiscipline()]) == []


# ---------------------------------------------------------------------------
# wire-spec-drift
# ---------------------------------------------------------------------------

def _drift_tree(tmp_path, *, tag_rows, version_line, code_tag_extra=""):
    return write_tree(tmp_path, {
        "docs/ARCH.md": f"""\
            ### 3.3 tags

            | tag (`"__w"` value) | encodes |
            |---|---|
            {tag_rows}

            {version_line}

            ```
            {{"type": "hello"}}
            {{"type": "stop"}}
            ```
        """,
        "docs/OBS.md": """\
            ## What is instrumented

            | Name | Kind | Where |
            |---|---|---|
            | `w.frames_in/out` | counter | per stream |
            | `m.pack` / `m.unpack` | span | phases |

            ## Next
        """,
        "src/pkg/__init__.py": "",
        "src/pkg/wire.py": f"""\
            _TAG = "__w"
            def enc(o):
                return {{_TAG: "none"}}{code_tag_extra}
            def dec(tag):
                if tag == "none":
                    return None
            def msgs(s):
                s.put({{"type": "hello"}})
                s.put({{"type": "stop"}})
        """,
        "src/pkg/ser.py": """\
            VERSION = 2
            READABLE_VERSIONS = (1, 2)
        """,
        "src/pkg/user.py": """\
            from pkg import obs
            def f():
                with obs.span("m.pack"):
                    pass
                with obs.span("m.unpack"):
                    pass
                obs.count("w.frames_in")
                obs.count("w.frames_out")
        """,
    })

DRIFT_CFG = dict(
    JAX_CFG, jax_free_modules=[],
    architecture_doc="docs/ARCH.md", observability_doc="docs/OBS.md",
    wire_tag_files=["src/pkg/wire.py"],
    wire_message_files=["src/pkg/wire.py"],
    serialization_file="src/pkg/ser.py", obs_scope=["src"])

GOOD_TAGS = '| `"none"`  | `None` |'
GOOD_VER = "Current version is 2; readers accept 1 and 2."

def test_drift_clean_when_docs_match_code(tmp_path):
    _drift_tree(tmp_path, tag_rows=GOOD_TAGS, version_line=GOOD_VER)
    assert lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()]) == []

def test_drift_catches_corrupted_tag_table(tmp_path):
    # the doc documents a tag the code never handles, and the code's
    # "none" tag vanished from the doc
    _drift_tree(tmp_path, tag_rows='| `"ghost"` | nothing |',
                version_line=GOOD_VER)
    msgs = [f.message for f in
            lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()])]
    assert any('"ghost"' in m and "never produced" in m for m in msgs)
    assert any('"none"' in m and "missing from" in m for m in msgs)

def test_drift_catches_version_mismatch(tmp_path):
    _drift_tree(tmp_path, tag_rows=GOOD_TAGS,
                version_line="Current version is 3; readers accept 3.")
    msgs = [f.message for f in
            lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()])]
    assert any("VERSION=2" in m for m in msgs)
    assert any("READABLE_VERSIONS" in m for m in msgs)

def test_drift_catches_undocumented_message_type(tmp_path):
    root = _drift_tree(tmp_path, tag_rows=GOOD_TAGS, version_line=GOOD_VER)
    wire = root / "src/pkg/wire.py"
    wire.write_text(wire.read_text() +
                    '\ndef extra(s):\n    s.put({"type": "rogue"})\n')
    msgs = [f.message for f in
            lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()])]
    assert any('"rogue"' in m and "appears nowhere" in m for m in msgs)

def test_drift_catches_obs_name_drift(tmp_path):
    root = _drift_tree(tmp_path, tag_rows=GOOD_TAGS, version_line=GOOD_VER)
    user = root / "src/pkg/user.py"
    user.write_text(user.read_text()
                    + '\ndef g():\n    obs.gauge("w.depth", 1)\n')
    msgs = [f.message for f in
            lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()])]
    assert any('"w.depth"' in m for m in msgs)

def test_drift_sees_recorded_spans_and_the_planes_own(tmp_path):
    # a span recorded after the fact, and one the telemetry plane
    # records by itself (a bare call inside src/repro/obs/), both
    # undocumented
    root = _drift_tree(tmp_path, tag_rows=GOOD_TAGS, version_line=GOOD_VER)
    user = root / "src/pkg/user.py"
    user.write_text(user.read_text()
                    + '\ndef g():\n    obs.record("m.compile", 0, 1)\n')
    plane = root / "src/repro/obs/telemetry.py"
    plane.parent.mkdir(parents=True)
    plane.write_text('def hook():\n    record("h.gc", 0, 1)\n')
    msgs = [f.message for f in
            lint(tmp_path, DRIFT_CFG, rules=[WireSpecDrift()])]
    assert any('"m.compile" (span)' in m for m in msgs)
    assert any('"h.gc" (span)' in m for m in msgs)

def test_obs_table_suffix_expansion():
    names = parse_obs_table(
        "## What is instrumented\n\n"
        "| Name | Kind | Where |\n|---|---|---|\n"
        "| `wire.frames_in/out`, `wire.bytes_in/out` | counter | x |\n"
        "| `mig.pack` / `mig.transfer` | span | y |\n")
    assert set(names) == {"wire.frames_in", "wire.frames_out",
                          "wire.bytes_in", "wire.bytes_out",
                          "mig.pack", "mig.transfer"}
    assert names["wire.bytes_out"][0] == "counter"


# ---------------------------------------------------------------------------
# engine policies: parse errors, unknown rules, stable ids
# ---------------------------------------------------------------------------

def test_parse_error_is_a_finding(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/broken.py": "def f(:\n",
    })
    fs = lint(tmp_path, dict(JAX_CFG, jax_free_modules=[]), rules=[])
    assert rules_of(fs) == ["parse-error"]

def test_unknown_rule_in_marker_is_a_finding(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": "x = 1  # repro-lint: allow[no-such-rule] oops\n",
    })
    fs = lint(tmp_path, dict(JAX_CFG, jax_free_modules=[]),
              rules=[NoPickleOnWire()])
    assert rules_of(fs) == ["bad-suppression"]
    assert "no-such-rule" in fs[0].message

def test_parse_error_cannot_be_suppressed(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "src/pkg/broken.py":
            "# repro-lint: allow[parse-error] nope\ndef f(:\n",
    })
    fs = lint(tmp_path, dict(JAX_CFG, jax_free_modules=[]), rules=[])
    assert "parse-error" in rules_of(fs)

def test_finding_ids_stable_under_line_shift(tmp_path):
    files = {
        "src/pkg/__init__.py": "",
        "src/pkg/m.py": "import random\n",
    }
    write_tree(tmp_path, files)
    cfg = dict(JAX_CFG, jax_free_modules=[], random_scope=["src"])
    first = lint(tmp_path, cfg, rules=[DeterministicIteration()])
    # prepend a comment: line number changes, id must not
    (tmp_path / "src/pkg/m.py").write_text("# pad\nimport random\n")
    second = lint(tmp_path, cfg, rules=[DeterministicIteration()])
    assert first[0].fid == second[0].fid
    assert first[0].line != second[0].line


# ---------------------------------------------------------------------------
# the CLI and the repo itself
# ---------------------------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd, env=env)

def test_repo_lints_clean():
    """The tier-1 gate: the tree must satisfy its own contracts, with
    every suppression carrying a reason."""
    findings = run_analysis(REPO)
    assert findings == [], "\n".join(f.format() for f in findings)

def test_cli_json_output_clean():
    res = _run_cli("--json")
    assert res.returncode == 0, res.stdout + res.stderr
    doc = json.loads(res.stdout)
    assert doc["count"] == 0 and doc["findings"] == []

def test_cli_nonzero_on_parse_error(tmp_path):
    write_tree(tmp_path, {"src/repro/__init__.py": "",
                          "src/repro/bad.py": "def f(:\n"})
    res = _run_cli("--root", str(tmp_path))
    assert res.returncode == 1
    assert "parse-error" in res.stdout

def test_cli_nonzero_on_unknown_suppression_rule(tmp_path):
    write_tree(tmp_path, {
        "src/repro/__init__.py": "",
        "src/repro/m.py": "x = 1  # repro-lint: allow[not-a-rule] why\n"})
    res = _run_cli("--root", str(tmp_path))
    assert res.returncode == 1
    assert "bad-suppression" in res.stdout

def test_cli_json_out_artifact(tmp_path):
    out = tmp_path / "findings.json"
    res = _run_cli("--json-out", str(out))
    assert res.returncode == 0
    assert json.loads(out.read_text())["count"] == 0

def test_rule_names_unique_and_documented():
    rules = all_rules()
    names = [r.name for r in rules]
    assert len(names) == len(set(names))
    assert all(r.contract for r in rules)
    doc = (REPO / "docs" / "ANALYSIS.md").read_text()
    for name in names:
        assert name in doc, f"docs/ANALYSIS.md does not mention {name}"


# ---------------------------------------------------------------------------
# consolidated checkers keep their engines
# ---------------------------------------------------------------------------

def test_trace_checker_engine():
    good = {"traceEvents": [
        {"ph": "X", "name": "a", "ts": 1.0, "dur": 2.0,
         "pid": 1, "tid": 1},
        {"ph": "M", "name": "process_name", "args": {"name": "rank0"}},
        {"ph": "C", "name": "c", "ts": 1.0, "pid": 1,
         "args": {"v": 3}},
    ]}
    assert check_trace(good) == []
    assert check_trace(good, require_ranks=2)
    assert check_trace(good, require_spans=["missing"])
    bad = {"traceEvents": [{"ph": "X", "name": "a"}]}
    assert check_trace(bad)

def test_doc_link_rule_flags_broken_link(tmp_path):
    write_tree(tmp_path, {
        "src/pkg/__init__.py": "",
        "README.md": "see [gone](no/such/file.md)\n",
    })
    from repro.analysis.doclinks import DocLinks
    fs = lint(tmp_path, dict(JAX_CFG, jax_free_modules=[],
                             doc_link_root="."), rules=[DocLinks()])
    assert rules_of(fs) == ["doc-links"]
    assert "no/such/file.md" in fs[0].message
