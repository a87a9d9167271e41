"""apexlint (ISSUE 19): each AST rule must CATCH its planted bug and
PASS the real tree.

Mirrors tests/test_analysis.py's contract for the graph sanitizers:
every rule in :data:`apex_tpu.analysis.staticcheck.RULES` gets a
seeded-violation fixture (a tiny tmp-tree file exhibiting exactly the
bug class the rule encodes) plus a clean twin proving the rule does
not fire on the disciplined form.  On top: suppression counting and
hygiene, the env-registry ↔ README drift gate (a doctored README must
fail), the jax-free CLI end to end, and the
:mod:`apex_tpu.analysis.dataflow` jaxpr pass catching a planted
closure-captured donated scan carry.

Fixture hygiene note: this file is itself INSIDE the sweep, so planted
bait lives only inside snippet strings (never as standalone
``APEX_TPU_*`` constants), and suppression-comment text is assembled
at runtime so the line scanner never sees the literal token here.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from apex_tpu import envs
from apex_tpu.analysis import staticcheck as sc

REPO = sc.REPO_ROOT


def _sup(rule, reason=None):
    """Assemble a suppression comment without the literal token
    appearing in this file's source (it would be counted)."""
    tail = f": disable={rule}"
    if reason:
        tail += f" -- {reason}"
    return "# apexlint" + tail


def _plant(tmp_path, relpath, source):
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return relpath


def _scan_one(tmp_path, relpath, source):
    rel = _plant(tmp_path, relpath, source)
    return sc.scan_files([rel], root=str(tmp_path))


def _rules_hit(report):
    return {f.rule for f in report.findings}


# ---------------------------------------------------------------------------
# the registry itself
# ---------------------------------------------------------------------------

class TestRuleRegistry:
    def test_shape(self):
        """>= 8 active rules, unique kebab-case names, every rule
        cites its originating bug class."""
        names = [r.name for r in sc.RULES]
        assert len(names) == len(set(names))
        assert len(sc.RULES) >= 8
        for r in sc.RULES:
            assert r.origin and r.doc, r.name
            assert r.scope in ("all", "nontest", "deterministic")
            assert r.name == r.name.lower() and " " not in r.name

    def test_every_checker_registered(self):
        """Every per-file checker maps to a registered rule; the two
        non-checker rules are the line scanner and the cross-artifact
        drift gate."""
        rule_names = {r.name for r in sc.RULES}
        assert set(sc._CHECKERS) <= rule_names
        assert rule_names - set(sc._CHECKERS) == {
            "env-doc-drift", "suppression-hygiene",
        }


# ---------------------------------------------------------------------------
# one seeded violation per rule (+ the clean twin)
# ---------------------------------------------------------------------------

class TestWallClock:
    def test_planted_in_deterministic_module(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/obs/flightrec.py", """\
            import time
            def stamp():
                return time.time()
            """)
        assert "wall-clock-in-deterministic" in _rules_hit(report)

    def test_planted_in_digest_function(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/anywhere.py", """\
            import time
            def plan_digest():
                return hash(time.perf_counter())
            """)
        assert "wall-clock-in-deterministic" in _rules_hit(report)

    def test_clean_outside_deterministic_scope(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/anywhere.py", """\
            import time
            def span():
                return time.perf_counter()
            """)
        assert "wall-clock-in-deterministic" not in _rules_hit(report)


class TestUnseededRng:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/gen.py", """\
            import random
            import numpy as np
            def noise():
                return np.random.rand(3) + random.uniform(0, 1)
            """)
        hits = [f for f in report.findings if f.rule == "unseeded-rng"]
        assert len(hits) == 2

    def test_clean_seeded(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/gen.py", """\
            import numpy as np
            def noise(seed):
                rng = np.random.RandomState(seed)
                return rng.rand(3)
            """)
        assert "unseeded-rng" not in _rules_hit(report)


class TestNonatomicJsonWrite:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/store.py", """\
            import json
            def save(path, doc):
                with open(path, "w") as f:
                    json.dump(doc, f)
            """)
        assert "nonatomic-json-write" in _rules_hit(report)

    def test_clean_tmp_replace(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/store.py", """\
            import json
            import os
            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, path)
            """)
        assert "nonatomic-json-write" not in _rules_hit(report)


class TestEnvKnobRegistry:
    def test_planted_unregistered_read(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/knob.py", """\
            import os
            def read():
                return os.environ.get("APEX_TPU_TOTALLY_FAKE_KNOB", "0")
            """)
        hits = [f for f in report.findings
                if f.rule == "unregistered-env-knob"]
        assert len(hits) == 1
        assert "APEX_TPU_" + "TOTALLY_FAKE_KNOB" in hits[0].message

    def test_clean_registered_read(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/knob.py", """\
            import os
            def read():
                return os.environ.get("APEX_TPU_PAGED_KV", "1")
            """)
        assert "unregistered-env-knob" not in _rules_hit(report)

    def test_registered_helpers(self, monkeypatch):
        """The runtime twin of the static rule: registered reads work,
        unregistered reads raise."""
        monkeypatch.delenv("APEX_TPU_PAGED_KV", raising=False)
        assert envs.get("APEX_TPU_PAGED_KV") == "1"
        assert envs.flag("APEX_TPU_PAGED_KV") is True
        monkeypatch.setenv("APEX_TPU_PAGED_KV", "0")
        assert envs.flag("APEX_TPU_PAGED_KV") is False
        monkeypatch.delenv("APEX_TPU_MICROBATCHES", raising=False)
        assert envs.integer("APEX_TPU_MICROBATCHES") == 1
        fake = "APEX_TPU_" + "TOTALLY_FAKE_KNOB"
        for fn in (envs.get, envs.flag, envs.integer):
            with pytest.raises(KeyError):
                fn(fake)
        assert envs.is_registered("APEX_TPU_PAGED_KV")
        assert not envs.is_registered(fake)


class TestEnvDocDrift:
    def _readme(self):
        with open(os.path.join(REPO, "README.md")) as f:
            return f.read()

    def test_real_readme_in_sync(self):
        assert envs.check_readme_drift(self._readme()) == []

    def test_removed_row_detected(self, tmp_path):
        """The acceptance planted drift: delete one documented knob's
        README row and the sweep must go nonzero."""
        text = "\n".join(
            line for line in self._readme().splitlines()
            if not line.startswith("| `APEX_TPU_PAGED_KV`")
        )
        errs = envs.check_readme_drift(text)
        assert any("APEX_TPU_PAGED_KV" in e and "no README" in e
                   for e in errs)
        doctored = tmp_path / "README.md"
        doctored.write_text(text)
        report = sc.scan_files([], root=REPO, readme=str(doctored))
        drift = [f for f in report.findings if f.rule == "env-doc-drift"]
        assert drift and report.census()["violations"] > 0

    def test_phantom_row_detected(self):
        row = "| `APEX_TPU_" + "PHANTOM_KNOB` | `0` | nothing |"
        errs = envs.check_readme_drift(self._readme() + "\n" + row)
        assert any("PHANTOM_KNOB" in e and "no such knob" in e
                   for e in errs)


class TestClockIntoFlightrec:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/wire.py", """\
            from apex_tpu import obs
            def mk(clock):
                return obs.FlightRecorder(clock=clock, enabled=True)
            """)
        assert "clock-into-flightrec" in _rules_hit(report)

    def test_clean_default_and_none(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/wire.py", """\
            from apex_tpu import obs
            def mk():
                a = obs.FlightRecorder(enabled=True)
                b = obs.GangTelemetry(clock=None)
                return a, b
            """)
        assert "clock-into-flightrec" not in _rules_hit(report)


class TestUseAfterDonate:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/win.py", """\
            import jax
            def window(step_fn, state, xs):
                step = jax.jit(step_fn, donate_argnums=(1,))
                out = step(xs, state)
                return out, state
            """)
        hits = [f for f in report.findings
                if f.rule == "use-after-donate"]
        assert len(hits) == 1
        assert "'state'" in hits[0].message

    def test_clean_rebind(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/win.py", """\
            import jax
            def window(step_fn, state, xs):
                step = jax.jit(step_fn, donate_argnums=(1,))
                state = step(xs, state)
                return state
            """)
        assert "use-after-donate" not in _rules_hit(report)


class TestUnsortedWalk:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/sweep.py", """\
            import glob
            import os
            def names(d):
                a = os.listdir(d)
                b = glob.glob(d + "/*.json")
                return a + b
            """)
        hits = [f for f in report.findings if f.rule == "unsorted-walk"]
        assert len(hits) == 2

    def test_clean_sorted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/sweep.py", """\
            import glob
            import os
            def names(d):
                a = sorted(os.listdir(d))
                b = sorted(glob.glob(d + "/*.json"))
                return a + b
            """)
        assert "unsorted-walk" not in _rules_hit(report)


class TestRecordKindKeyword:
    def test_planted(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/ev.py", """\
            def emit(fr):
                fr.record(kind="step_start", step=3)
            """)
        assert "record-kind-keyword" in _rules_hit(report)

    def test_clean_positional(self, tmp_path):
        report = _scan_one(tmp_path, "apex_tpu/ev.py", """\
            def emit(fr):
                fr.record("step_start", step=3, kind="data-attr-ok")
            """)
        assert "record-kind-keyword" not in _rules_hit(report)


# ---------------------------------------------------------------------------
# suppressions: counting + hygiene
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_suppression_quashes_and_counts(self, tmp_path):
        src = textwrap.dedent("""\
            import os
            def names(d):
                return os.listdir(d)  @SUP@
            """).replace("@SUP@", _sup("unsorted-walk",
                                       "order irrelevant, counted only"))
        rel = _plant(tmp_path, "apex_tpu/sweep.py", src)
        report = sc.scan_files([rel], root=str(tmp_path))
        c = report.census()
        assert c["violations"] == 0
        assert c["suppressions"] == 1
        assert len(report.suppressed) == 1
        assert report.suppressions[0].used is True
        assert report.suppressions[0].reason.startswith("order")

    def test_suppression_on_line_above(self, tmp_path):
        src = textwrap.dedent("""\
            import os
            def names(d):
                @SUP@
                return os.listdir(d)
            """).replace("@SUP@", _sup("unsorted-walk", "see above"))
        rel = _plant(tmp_path, "apex_tpu/sweep.py", src)
        report = sc.scan_files([rel], root=str(tmp_path))
        assert report.census()["violations"] == 0
        assert report.census()["suppressions"] == 1

    def test_bare_suppression_is_a_violation(self, tmp_path):
        src = "x = 1  " + _sup("unsorted-walk") + "\n"
        rel = _plant(tmp_path, "apex_tpu/bare.py", src)
        report = sc.scan_files([rel], root=str(tmp_path))
        hits = [f for f in report.findings
                if f.rule == "suppression-hygiene"]
        assert hits and "reason" in hits[0].message
        assert report.census()["suppressions"] == 0

    def test_unknown_rule_is_a_violation(self, tmp_path):
        src = "x = 1  " + _sup("no-such-rule", "whatever") + "\n"
        rel = _plant(tmp_path, "apex_tpu/bare.py", src)
        report = sc.scan_files([rel], root=str(tmp_path))
        hits = [f for f in report.findings
                if f.rule == "suppression-hygiene"]
        assert hits and "no-such-rule" in hits[0].message


# ---------------------------------------------------------------------------
# the real tree + the pinned census
# ---------------------------------------------------------------------------

class TestRealTree:
    def test_repo_is_clean(self):
        """The acceptance gate: zero violations on the current tree,
        census consistent with the lint_graphs pins (exact rules and
        suppressions, file floor)."""
        report = sc.scan_repo()
        assert report.findings == [], report.render()
        from tools.lint_graphs import APEXLINT_PINS

        c = report.census()
        assert c["rules"] == APEXLINT_PINS["rules"]
        assert c["suppressions"] == APEXLINT_PINS["suppressions"]
        assert c["files"] >= APEXLINT_PINS["files"]
        assert c["violations"] == 0

    def test_sweep_covers_the_tree(self):
        files = sc.iter_source_files()
        assert "apex_tpu/analysis/staticcheck.py" in files
        assert "tools/apexlint.py" in files
        assert "tests/test_staticcheck.py" in files
        assert "chip_smoke.py" in files


# ---------------------------------------------------------------------------
# the jax-free CLI
# ---------------------------------------------------------------------------

def _cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "apexlint.py"),
         *args],
        capture_output=True, text=True, cwd=cwd,
    )


class TestCli:
    def test_clean_repo_exits_zero(self):
        r = _cli()
        assert r.returncode == 0, r.stdout + r.stderr
        assert "0 violation(s)" in r.stdout

    def test_summary_banner(self):
        r = _cli("--summary")
        assert r.returncode == 0
        assert r.stdout.startswith("APEXLINT=pass")
        assert "violations=0" in r.stdout

    def test_json_census(self):
        r = _cli("--json")
        doc = json.loads(r.stdout)
        assert doc["schema"] == "apex_tpu.apexlint.v1"
        assert doc["census"]["violations"] == 0
        assert doc["census"]["rules"] == len(sc.RULES)

    def test_planted_tree_exits_nonzero(self, tmp_path):
        _plant(tmp_path, "apex_tpu/bad.py", """\
            import os
            def names(d):
                return os.listdir(d)
            """)
        r = _cli("--root", str(tmp_path))
        assert r.returncode == 1
        assert "unsorted-walk" in r.stdout

    def test_doctored_readme_exits_nonzero(self, tmp_path):
        with open(os.path.join(REPO, "README.md")) as f:
            text = "\n".join(
                line for line in f.read().splitlines()
                if not line.startswith("| `APEX_TPU_PAGED_KV`")
            )
        doctored = tmp_path / "README.md"
        doctored.write_text(text)
        r = _cli("--readme", str(doctored))
        assert r.returncode == 1
        assert "env-doc-drift" in r.stdout


# ---------------------------------------------------------------------------
# the jaxpr dataflow pass (donated scan closure captures)
# ---------------------------------------------------------------------------

class TestDonateDataflow:
    def _mk(self):
        import jax.numpy as jnp

        return {"w": jnp.ones(4)}, jnp.ones((3, 4))

    def test_planted_closure_capture(self):
        from jax import lax

        from apex_tpu.analysis import dataflow

        def window(state, xs):
            anchor = state["w"]

            def body(c, x):
                return c + x * anchor, None

            out, _ = lax.scan(body, state["w"] * 1.0, xs)
            return {"w": out}

        state, xs = self._mk()
        found = dataflow.scan_donated_captures(
            window, state, xs, donate_argnums=(0,)
        )
        assert len(found) == 1
        assert found[0].argnum == 0 and "w" in found[0].path
        assert found[0].also_carry is False
        with pytest.raises(dataflow.ScanCaptureError):
            dataflow.assert_no_donated_captures(
                window, state, xs, donate_argnums=(0,), label="window"
            )

    def test_planted_const_and_carry(self):
        """The worst form: the SAME donated var is simultaneously the
        carry being overwritten and a const read every iteration."""
        from jax import lax

        from apex_tpu.analysis import dataflow

        def window(state, xs):
            anchor = state["w"]

            def body(c, x):
                return c + x * anchor, None

            out, _ = lax.scan(body, state["w"], xs)
            return {"w": out}

        state, xs = self._mk()
        found = dataflow.scan_donated_captures(
            window, state, xs, donate_argnums=(0,)
        )
        assert len(found) == 1 and found[0].also_carry is True

    def test_clean_non_donated_const(self):
        from jax import lax

        from apex_tpu.analysis import dataflow

        def window(state, xs, table):
            def body(c, x):
                return c + x * table, None

            out, _ = lax.scan(body, state["w"], xs)
            return {"w": out}

        state, xs = self._mk()
        import jax.numpy as jnp

        assert dataflow.scan_donated_captures(
            window, state, xs, jnp.ones(4), donate_argnums=(0,)
        ) == []

    def test_capture_through_pjit(self):
        import jax
        from jax import lax

        from apex_tpu.analysis import dataflow

        def inner(w, xs):
            def body(c, x):
                return c + x * w, None

            return lax.scan(body, w * 1.0, xs)[0]

        def window(state, xs):
            return {"w": jax.jit(inner)(state["w"], xs)}

        state, xs = self._mk()
        found = dataflow.scan_donated_captures(
            window, state, xs, donate_argnums=(0,)
        )
        assert len(found) == 1
