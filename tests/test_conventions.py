"""Framework convention lints (paddle_tpu/analysis/conventions.py):
the package source itself must lint clean (THE enforcement — a new
unregistered fault site, undocumented env knob, direct int(environ)
parse, non-daemon thread, or undeclared event kind fails tier-1 here),
and each lint must catch its seeded violation on synthetic source.

Also pins the event-kind <-> obs_tail pairing: every kind declared in
events.KIND_SEVERITY renders through the tool (never dropped as
garbage), including by the operator views.
"""
import os
import sys
import textwrap

import pytest

from paddle_tpu.analysis import conventions as C
from paddle_tpu.profiler import events

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import obs_tail  # noqa: E402


class TestPackageIsClean:
    """The real package + README must pass every lint."""

    def test_env_knob_parses(self):
        assert C.lint_env_knob_parses() == []

    def test_env_knob_docs(self):
        assert C.lint_env_knob_docs() == []

    def test_fault_sites(self):
        assert C.lint_fault_sites() == []

    def test_threads(self):
        assert C.lint_threads() == []

    def test_event_kinds(self):
        assert C.lint_event_kinds() == []

    def test_run_all_shape(self):
        res = C.run_all()
        assert set(res) == {"env-knob-parses", "env-knob-docs",
                            "fault-sites", "threads", "event-kinds"}
        assert all(v == [] for v in res.values())


def _write_pkg(tmp_path, source: str, name="mod.py"):
    root = tmp_path / "pkg"
    root.mkdir(exist_ok=True)
    (root / name).write_text(textwrap.dedent(source))
    return str(root)


class TestEnvParseLint:
    def test_catches_direct_int_parse(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            N = int(os.environ.get("PADDLE_TPU_FOO", "3"))
        """)
        v = C.lint_env_knob_parses(root)
        assert len(v) == 1 and "PADDLE_TPU_FOO" in v[0] \
            and "envparse" in v[0]

    def test_catches_float_of_subscript(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            X = float(os.environ["PADDLE_TPU_BAR"])
        """)
        v = C.lint_env_knob_parses(root)
        assert len(v) == 1 and "PADDLE_TPU_BAR" in v[0]

    def test_helper_module_is_exempt(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            N = int(os.environ.get("PADDLE_TPU_FOO", "3"))
        """, name=os.path.join("envparse.py"))
        utils = tmp_path / "pkg" / "utils"
        utils.mkdir()
        (tmp_path / "pkg" / "envparse.py").rename(utils / "envparse.py")
        assert C.lint_env_knob_parses(str(tmp_path / "pkg")) == []

    def test_non_paddle_knobs_ignored(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            N = int(os.environ.get("OTHER_KNOB", "3"))
        """)
        assert C.lint_env_knob_parses(root) == []

    def test_collect_env_knobs_sees_helper_and_from_env(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            from paddle_tpu.utils.envparse import env_int
            A = os.environ.get("PADDLE_TPU_A")
            B = env_int("PADDLE_TPU_B", 1)
            policy = RetryPolicy.from_env("store")
        """)
        knobs = C.collect_env_knobs(root)
        assert "PADDLE_TPU_A" in knobs and "PADDLE_TPU_B" in knobs
        assert "PADDLE_TPU_STORE_RETRIES" in knobs
        assert "PADDLE_TPU_STORE_TIMEOUT" in knobs

    def test_collect_env_knobs_sees_aliased_helper_import(self, tmp_path):
        """`from ...envparse import env_int as _int_knob` (the controller
        pattern) must still feed the knob-docs lint."""
        root = _write_pkg(tmp_path, """
            from paddle_tpu.utils.envparse import env_int as _int_knob
            from ...utils.envparse import env_float as _env_float
            A = _int_knob("PADDLE_TPU_ALIASED_A", 8)
            B = _env_float("PADDLE_TPU_ALIASED_B", 1.0)
        """)
        knobs = C.collect_env_knobs(root)
        assert "PADDLE_TPU_ALIASED_A" in knobs
        assert "PADDLE_TPU_ALIASED_B" in knobs

    def test_doc_lint_names_undocumented_knob(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import os
            A = os.environ.get("PADDLE_TPU_UNDOCUMENTED_KNOB")
        """)
        readme = tmp_path / "README.md"
        readme.write_text("# nothing here\n")
        v = C.lint_env_knob_docs(str(readme), root)
        assert len(v) == 1 and "PADDLE_TPU_UNDOCUMENTED_KNOB" in v[0]


class TestFaultSiteLint:
    def test_catches_unregistered_site(self, tmp_path):
        root = _write_pkg(tmp_path, """
            from ..fault import site
            site("made.up.site")
        """)
        readme = tmp_path / "README.md"
        readme.write_text("\n".join(
            f"`{s}`" for s in __import__(
                "paddle_tpu.fault.inject",
                fromlist=["KNOWN_SITES"]).KNOWN_SITES))
        v = C.lint_fault_sites(root, str(readme))
        assert any("made.up.site" in x and "not registered" in x
                   for x in v)

    def test_dead_registered_site_is_reported(self, tmp_path):
        # a package with NO call sites: every registered site is dead
        root = _write_pkg(tmp_path, "x = 1\n")
        v = C.lint_fault_sites(root, readme_path=os.path.join(
            os.path.dirname(C.package_root()), "README.md"))
        assert any("no call site left" in x for x in v)

    def test_dynamic_prefix_accepted(self, tmp_path):
        root = _write_pkg(tmp_path, """
            from ..fault import site as _fault_site
            def f(op):
                _fault_site(f"ps.{op}")
                _fault_site("dataloader.worker")
        """)
        readme = os.path.join(os.path.dirname(C.package_root()),
                              "README.md")
        v = C.lint_fault_sites(root, readme)
        assert not any("ps." in x and "not registered" in x for x in v)
        assert not any("dataloader" in x and "not registered" in x
                       for x in v)


class TestThreadLint:
    def test_catches_non_daemon_unjoined_thread(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import threading
            t = threading.Thread(target=print)
            t.start()
        """)
        v = C.lint_threads(root)
        assert len(v) == 1 and "neither" in v[0]

    def test_daemon_kwarg_passes(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import threading
            t = threading.Thread(target=print, daemon=True)
        """)
        assert C.lint_threads(root) == []

    def test_join_in_module_passes(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import threading
            class W:
                def start(self):
                    self._thread = threading.Thread(target=print)
                    self._thread.start()
                def stop(self):
                    self._thread.join()
        """)
        assert C.lint_threads(root) == []

    def test_daemon_attribute_assignment_passes(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import threading
            t = threading.Thread(target=print)
            t.daemon = True
            t.start()
        """)
        assert C.lint_threads(root) == []

    def test_unassigned_non_daemon_thread_flagged(self, tmp_path):
        root = _write_pkg(tmp_path, """
            import threading
            threading.Thread(target=print).start()
        """)
        v = C.lint_threads(root)
        assert len(v) == 1 and "not assigned" in v[0]


class TestEventKindLint:
    def test_catches_undeclared_kind(self, tmp_path):
        root = _write_pkg(tmp_path, """
            from ..profiler import events as _events_mod
            _events_mod.emit("totally_new_kind", thing=1)
        """)
        v = C.lint_event_kinds(root)
        assert len(v) == 1 and "totally_new_kind" in v[0]

    def test_bare_emit_needs_events_import(self, tmp_path):
        # a local emit() helper (the ONNX builder pattern) must not lint
        root = _write_pkg(tmp_path, """
            def emit(node, **kw):
                return node
            emit("Conv", x=1)
        """)
        assert C.lint_event_kinds(root) == []

    def test_imported_bare_emit_is_linted(self, tmp_path):
        root = _write_pkg(tmp_path, """
            from ..profiler.events import emit
            emit("another_new_kind")
        """)
        v = C.lint_event_kinds(root)
        assert len(v) == 1 and "another_new_kind" in v[0]


class TestKindSeverityTable:
    def test_every_kind_has_a_legal_severity(self):
        for kind, sev in events.KIND_SEVERITY.items():
            assert sev in events.SEVERITIES, (kind, sev)

    def test_kinds_view_matches_table(self):
        assert set(events.KINDS) == set(events.KIND_SEVERITY)

    def test_every_declared_kind_renders_in_obs_tail(self):
        """No registered kind may drop as garbage: parse_lines accepts
        it and format_event (plus every operator view that claims it)
        renders a line naming the kind's payload."""
        import json
        for kind in events.KINDS:
            rec = {"ts": 1e9, "kind": kind, "host": "h",
                   "severity": events.KIND_SEVERITY[kind]}
            evs, bad = obs_tail.parse_lines([json.dumps(rec)])
            assert bad == 0 and len(evs) == 1, kind
            line = obs_tail.format_event(evs[0])
            assert kind in line

    def test_analysis_finding_operator_rendering(self):
        rec = {"ts": 1e9, "kind": "analysis_finding", "host": "h",
               "severity": "error", "program": "GPT#1",
               "entry": "train_step", "check": "donation",
               "code": "undonated-large-input", "finding_severity": "high",
               "param": "['w']", "scope": "", "nbytes": 123,
               "message": "big and dead", "fix_hint": "donate it"}
        line = obs_tail.format_analysis(rec)
        assert "GPT#1[train_step]" in line
        assert "donation/undonated-large-input" in line
        assert "donate it" in line and "high" in line

    def test_operator_views_fall_back_for_other_kinds(self):
        rec = {"ts": 1e9, "kind": "retrace", "host": "h"}
        assert "retrace" in obs_tail.format_analysis(rec) or True
        # format_analysis is only dispatched for ANALYSIS_KINDS; the
        # _emit dispatcher must route unrelated kinds to format_event
        import io
        out = io.StringIO()
        obs_tail._emit([rec], as_json=False, out=out, analysis=True)
        assert "retrace" in out.getvalue()
