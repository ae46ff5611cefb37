"""graftcheck rule tests: one positive and one negative fixture per
rule, the suppression contract (justification REQUIRED), both
reporters, the CLI exit code, and the tier-1 gate that keeps
``dlrover_tpu/`` at zero unsuppressed findings.

These are pure-AST tests — no jax import, no devices.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools.graftcheck import check_source, run_paths, RULES
from tools.graftcheck.engine import render_human, render_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(src: str):
    """Unsuppressed rule ids triggered by a source snippet."""
    return {
        f.rule for f in check_source(textwrap.dedent(src))
        if not f.suppressed
    }


class TestJaxRules:
    def test_jx001_traced_branch_in_jit(self):
        assert "JX001" in rules_of("""
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)

    def test_jx001_traced_while(self):
        assert "JX001" in rules_of("""
            import jax

            def step(carry):
                while carry > 0:
                    carry = carry - 1
                return carry

            run = jax.jit(step)
        """)

    def test_jx001_negative_static_branches(self):
        # None-checks, len() (static shape), and un-jitted functions
        # all stay silent.
        assert "JX001" not in rules_of("""
            import jax

            @jax.jit
            def f(x, y=None):
                if y is None:
                    return x
                if len(x) > 2:
                    return x + y
                return x

            def plain(x):
                if x > 0:
                    return x
                return -x
        """)

    def test_jx001_name_collision_is_scoped(self):
        # A method sharing its name with a nested jitted helper must
        # not inherit jit scope (the rl/engine.py shape).
        assert "JX001" not in rules_of("""
            import jax

            class Engine:
                def build(self):
                    def generate(params, x):
                        return x
                    return jax.jit(generate)

                def generate(self, x):
                    if x not in self.cache:
                        self.cache[x] = self.build()
                    return self.cache[x]
        """)

    def test_jx002_host_sync_in_jit(self):
        src = """
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                s = float(x.sum())
                t = x.item()
                u = np.asarray(x)
                x.block_until_ready()
                return s + t
        """
        findings = [
            f for f in check_source(textwrap.dedent(src))
            if f.rule == "JX002"
        ]
        assert len(findings) == 4

    def test_jx002_negative_outside_jit(self):
        assert "JX002" not in rules_of("""
            import numpy as np

            def summarize(x):
                return float(x.sum()) + x.item() + np.asarray(x)[0]
        """)

    def test_jx003_jit_in_loop(self):
        assert "JX003" in rules_of("""
            import jax

            fns = []
            for i in range(3):
                fns.append(jax.jit(lambda x: x + i))
        """)

    def test_jx003_negative_jit_in_function_called_from_loop(self):
        assert "JX003" not in rules_of("""
            import jax

            def make():
                return jax.jit(lambda x: x)

            for i in range(3):
                make()
        """)

    def test_jx004_key_reused_twice(self):
        assert "JX004" in rules_of("""
            import jax

            def f(key):
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
        """)

    def test_jx004_key_reused_in_loop(self):
        assert "JX004" in rules_of("""
            import jax

            def f(key):
                out = []
                for _ in range(3):
                    out.append(jax.random.normal(key, (2,)))
                return out
        """)

    def test_jx004_with_statement_binding_does_not_crash(self):
        # withitems carry no lineno; the binding walk must use the
        # With statement's line instead of crashing.
        got = rules_of("""
            import jax

            def f(key, path):
                with open(path) as fh:
                    fh.read()
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return a + b
        """)
        assert "JX004" in got

    def test_jx004_with_as_rebinding_counts(self):
        assert "JX004" not in rules_of("""
            import jax

            def f(key, mgr):
                a = jax.random.normal(key, (2,))
                with mgr() as key:
                    b = jax.random.uniform(key, (2,))
                return a + b
        """)

    def test_jx004_negative_split_between_uses(self):
        assert "JX004" not in rules_of("""
            import jax

            def f(key):
                k1, k2 = jax.random.split(key)
                a = jax.random.normal(k1, (2,))
                b = jax.random.uniform(k2, (2,))
                return a + b

            def g(key):
                out = []
                for _ in range(3):
                    key, sub = jax.random.split(key)
                    out.append(jax.random.normal(sub, (2,)))
                return out
        """)

    def test_jx005_unhashable_static_arg(self):
        assert "JX005" in rules_of("""
            import jax

            def g(x, shape):
                return x.reshape(shape)

            f = jax.jit(g, static_argnums=(1,))
            y = f(x, [4, 4])
        """)

    def test_jx005_negative_tuple_static_arg(self):
        assert "JX005" not in rules_of("""
            import jax

            def g(x, shape):
                return x.reshape(shape)

            f = jax.jit(g, static_argnums=(1,))
            y = f(x, (4, 4))
        """)


class TestConcurrencyRules:
    def test_cc101_mixed_locked_unlocked_writes(self):
        assert "CC101" in rules_of("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def inc(self):
                    with self._lock:
                        self.n += 1

                def reset(self):
                    self.n = 0
        """)

    def test_cc101_negative_all_writes_locked(self):
        # __init__ writes don't count: no other thread exists yet.
        assert "CC101" not in rules_of("""
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def inc(self):
                    with self._lock:
                        self.n += 1

                def reset(self):
                    with self._lock:
                        self.n = 0
        """)

    def test_cc102_sleep_under_lock(self):
        assert "CC102" in rules_of("""
            import threading
            import time

            class Poller:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self):
                    with self._lock:
                        time.sleep(1.0)
        """)

    def test_cc102_negative_sleep_outside_lock(self):
        assert "CC102" not in rules_of("""
            import threading
            import time

            class Poller:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self):
                    with self._lock:
                        n = 1
                    time.sleep(1.0)
        """)

    def test_cc103_unjoined_nondaemon_thread(self):
        assert "CC103" in rules_of("""
            import threading

            t = threading.Thread(target=print)
            t.start()
        """)

    def test_cc103_anonymous_nondaemon_thread(self):
        assert "CC103" in rules_of("""
            import threading

            threading.Thread(target=print).start()
        """)

    def test_cc103_negative_daemon_or_joined(self):
        assert "CC103" not in rules_of("""
            import threading

            threading.Thread(target=print, daemon=True).start()

            t = threading.Thread(target=print)
            t.start()
            t.join()
        """)

    def test_cc104_broad_except_pass(self):
        assert "CC104" in rules_of("""
            try:
                x = 1
            except Exception:
                pass
        """)

    def test_cc104_bare_except_continue(self):
        assert "CC104" in rules_of("""
            for i in range(3):
                try:
                    x = 1
                except:
                    continue
        """)

    def test_cc104_negative_narrow_or_handled(self):
        assert "CC104" not in rules_of("""
            try:
                x = 1
            except OSError:
                pass

            try:
                y = 2
            except Exception as e:
                print(e)
        """)


class TestSuppression:
    SRC_UNJUSTIFIED = """
        try:
            x = 1
        # graftcheck: disable=CC104
        except Exception:
            pass
    """
    SRC_JUSTIFIED = """
        try:
            x = 1
        # graftcheck: disable=CC104 -- cleanup path must not raise
        except Exception:
            pass
    """

    def test_justified_suppression_suppresses(self):
        findings = check_source(textwrap.dedent(self.SRC_JUSTIFIED))
        assert all(f.suppressed for f in findings)
        (f,) = findings
        assert f.rule == "CC104"
        assert "cleanup path" in f.justification

    def test_unjustified_suppression_is_gc000_and_not_honored(self):
        got = rules_of(self.SRC_UNJUSTIFIED)
        assert got == {"GC000", "CC104"}

    def test_trailing_suppression_on_the_finding_line(self):
        assert rules_of("""
            try:
                x = 1
            except Exception:  # graftcheck: disable=CC104 -- teardown
                pass
        """) == set()

    def test_multiline_justification_attaches_to_next_code_line(self):
        findings = check_source(textwrap.dedent("""
            try:
                x = 1
            # graftcheck: disable=CC104 -- the justification wraps
            # over a second comment line before the except
            except Exception:
                pass
        """))
        (f,) = findings
        assert f.suppressed
        assert "second comment line" in f.justification

    def test_standalone_suppression_with_trailing_on_same_line(self):
        """A standalone suppression above a code line that carries its
        own trailing suppression: BOTH cover that line, and neither
        leaks onto the next one."""
        findings = check_source(textwrap.dedent("""
            import threading
            import time

            class P:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self):
                    with self._lock:
                        # graftcheck: disable=CC102 -- first deliberate
                        time.sleep(1.0)  # graftcheck: disable=CC102 -- same line
                        time.sleep(2.0)
        """))
        by_line = {f.line: f for f in findings if f.rule == "CC102"}
        lines = sorted(by_line)
        assert by_line[lines[0]].suppressed
        assert not by_line[lines[1]].suppressed

    def test_dangling_suppression_at_eof_is_reported(self):
        # A standalone suppression followed by no code line covers
        # nothing; it must surface as GC000, not vanish.
        findings = check_source(
            "x = 1\n# graftcheck: disable=CC102 -- orphaned\n"
        )
        (f,) = findings
        assert f.rule == "GC000"
        assert "covers nothing" in f.message

    def test_suppression_only_covers_named_rule(self):
        # A CC104 suppression must not hide a CC102 on the same line.
        got = rules_of("""
            import threading
            import time

            class P:
                def __init__(self):
                    self._lock = threading.Lock()

                def run(self):
                    with self._lock:
                        # graftcheck: disable=CC104 -- wrong rule id
                        time.sleep(1.0)
        """)
        assert "CC102" in got


class TestReporters:
    SRC = """
        try:
            x = 1
        except Exception:
            pass
    """

    def test_json_reporter_shape(self):
        findings = check_source(textwrap.dedent(self.SRC), "snippet.py")
        blob = json.loads(render_json(findings))
        assert blob["unsuppressed"] == 1
        assert blob["suppressed"] == 0
        (rec,) = blob["findings"]
        assert rec["rule"] == "CC104"
        assert rec["path"] == "snippet.py"
        assert rec["line"] == 4
        assert rec["suppressed"] is False

    def test_human_reporter_mentions_rule_and_location(self):
        findings = check_source(textwrap.dedent(self.SRC), "snippet.py")
        out = render_human(findings)
        assert "snippet.py:4: CC104" in out
        assert "1 finding(s)" in out

    def test_cli_exit_codes(self, tmp_path):
        dirty = tmp_path / "dirty.py"
        dirty.write_text(textwrap.dedent(self.SRC))
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck", str(dirty),
             "--format", "json"],
            capture_output=True, text=True, cwd=REPO, env=env,
        )
        assert r.returncode == 1, r.stderr
        assert json.loads(r.stdout)["unsuppressed"] == 1
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck", str(clean)],
            capture_output=True, text=True, cwd=REPO, env=env,
        )
        assert r.returncode == 0, r.stdout + r.stderr

    def test_non_utf8_file_is_a_finding_not_a_crash(self, tmp_path):
        bad = tmp_path / "latin1.py"
        bad.write_bytes(b"# -*- coding: latin-1 -*-\nx = '\xe9'\n")
        from tools.graftcheck import check_file

        (f,) = check_file(str(bad))
        assert f.rule == "GC000"
        assert "not valid UTF-8" in f.message
        assert not f.suppressed

    def test_cli_missing_path_fails_loudly(self, tmp_path):
        # A typo'd CI target must not pass as an empty "clean" tree.
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck",
             str(tmp_path / "no_such_dir")],
            capture_output=True, text=True, cwd=REPO, env=env,
        )
        assert r.returncode == 2, r.stdout
        assert "no such file or directory" in r.stderr


@pytest.mark.graftcheck
class TestRepoGate:
    """Tier-1 gate: the production tree stays graftcheck-clean under
    the full v2 rule set (per-file families AND the cross-module
    PC4xx/LK2xx/CH5xx/MT6xx families), and every suppression carries
    its written justification."""

    @pytest.fixture(scope="class")
    def repo_run(self):
        from tools.graftcheck.engine import run_project

        return run_project([os.path.join(REPO, "dlrover_tpu")])

    def test_dlrover_tpu_has_zero_unsuppressed_findings(
            self, repo_run):
        findings, _model = repo_run
        bad = [f for f in findings if not f.suppressed]
        assert not bad, "\n" + "\n".join(
            f"{f.path}:{f.line}: {f.rule} {f.message}" for f in bad
        )

    def test_every_suppression_is_justified(self, repo_run):
        findings, _model = repo_run
        suppressed = [f for f in findings if f.suppressed]
        assert suppressed, "expected the documented suppressions"
        for f in suppressed:
            assert f.justification.strip(), (
                f"{f.path}:{f.line} suppressed without justification"
            )

    def test_every_rule_id_is_documented(self):
        assert set(RULES) >= {
            "JX001", "JX002", "JX003", "JX004", "JX005",
            "CC101", "CC102", "CC103", "CC104", "GC000", "GC001",
            "OB301",
            "PC401", "PC402", "PC403", "PC404", "PC405",
            "LK201", "LK202",
            "CH501", "CH502", "CH503",
            "MT601", "MT602",
            "DET701", "DET702", "DET703", "DET704", "DET705",
        }

    def test_v2_families_are_live_not_vacuous(self, repo_run):
        """The cross-module rules must actually have a surface to
        check — an empty model would make the zero-findings gate a
        no-op."""
        findings, model = repo_run
        assert model.messages, "no message classes modeled"
        assert model.dispatch, "no dispatch tables modeled"
        assert model.call_sites, "no RpcClient.call sites modeled"
        assert model.chaos_sites, "no chaos SITES modeled"
        assert model.injects, "no chaos inject() sites modeled"
        assert model.counter_incs and model.gauge_regs, (
            "no metrics surface modeled"
        )
        assert model.test_text, "tests/ not found for CH503"
        # The documented deliberately-ephemeral master state rides
        # justified PC404 suppressions (diagnosis actions, network-
        # check rounds, speed telemetry) — they prove the journal rule
        # ran against the real servicer graph.
        assert any(f.rule == "PC404" and f.suppressed
                   for f in findings)

    def test_det_families_are_live_not_vacuous(self, repo_run):
        """The v3 pass has a real surface: every registry entry
        resolves in the tree (>= 8 of them), and the run-loop's
        documented wall-anchored site rides a justified DET701
        suppression — proof the effect closure ran against the real
        class graph, not an empty registry."""
        from tools.graftcheck.effect_rules import resolve_policy
        from tools.graftcheck.policy_registry import REGISTRY

        findings, model = repo_run
        assert len(REGISTRY) >= 8
        unresolved = [p.label for p in REGISTRY
                      if resolve_policy(model, p) is None]
        assert not unresolved, (
            f"registry entries do not resolve: {unresolved}"
        )
        assert any(f.rule == "DET701" and f.suppressed
                   for f in findings)

    def test_heartbeat_stays_destructive_retry_safe(self, repo_run):
        """Regression pin for the PR-2 Heartbeat bug: the heartbeat
        call site must never be marked idempotent (its handler pops
        DiagnosisActions).  If someone flips it, PC403 fires and the
        zero-findings gate breaks — this test names the contract."""
        _findings, model = repo_run
        hb = [cs for cs in model.call_sites if cs.msg == "Heartbeat"]
        assert hb, "Heartbeat call site not modeled"
        assert not any(cs.idempotent for cs in hb)


class TestObsRules:
    """OB301 (ISSUE 12): time.time() deltas used as durations."""

    def test_direct_wall_delta_flagged(self):
        assert "OB301" in rules_of("""
            import time
            def f(start):
                return time.time() - start
        """)

    def test_deadline_minus_now_flagged(self):
        assert "OB301" in rules_of("""
            import time
            def f(deadline):
                return deadline - time.time()
        """)

    def test_local_name_assigned_from_wall_clock_flagged(self):
        assert "OB301" in rules_of("""
            import time
            def f(last):
                now = time.time()
                return now - last
        """)

    def test_self_attr_assigned_from_wall_clock_flagged(self):
        assert "OB301" in rules_of("""
            import time
            class C:
                def start(self):
                    self._t0 = time.time()
                def elapsed(self):
                    now = time.monotonic()
                    return now - self._t0
        """)

    def test_or_default_idiom_tracked(self):
        assert "OB301" in rules_of("""
            import time
            def f(ts, then):
                now = ts or time.time()
                return now - then
        """)

    def test_monotonic_delta_not_flagged(self):
        src = """
            import time
            def f(start):
                deadline = time.monotonic() + 5.0
                return (time.monotonic() - start,
                        deadline - time.monotonic(),
                        time.perf_counter() - start)
        """
        assert "OB301" not in rules_of(src)

    def test_wall_sum_not_flagged(self):
        # Building a wall deadline is not the hazard; subtracting one
        # is (and THAT is what gets flagged, wherever it happens).
        assert "OB301" not in rules_of("""
            import time
            def f():
                return time.time() + 30.0
        """)

    def test_plain_timestamp_use_not_flagged(self):
        assert "OB301" not in rules_of("""
            import time
            def f(msg):
                msg.timestamp = time.time()
                return msg
        """)

    def test_suppression_honored_with_justification(self):
        findings = check_source(textwrap.dedent("""
            import time
            def f(file_mtime):
                # graftcheck: disable=OB301 -- vs a wall-clock mtime
                return time.time() - file_mtime
        """))
        ob = [f for f in findings if f.rule == "OB301"]
        assert len(ob) == 1 and ob[0].suppressed
        assert "mtime" in ob[0].justification


# ---------------------------------------------------------------------------
# graftcheck v2: whole-program protocol rules (ISSUE 14)
# ---------------------------------------------------------------------------

from tools.graftcheck import check_project, run_project  # noqa: E402
from tools.graftcheck.engine import render_chaos_table  # noqa: E402


def proj_rules(files, test_text=None):
    """Unsuppressed rule ids over a multi-file fixture project."""
    findings = check_project(
        {p: textwrap.dedent(s) for p, s in files.items()},
        test_text=test_text,
    )
    return {f.rule for f in findings if not f.suppressed}


def proj_findings(files, test_text=None):
    return check_project(
        {p: textwrap.dedent(s) for p, s in files.items()},
        test_text=test_text,
    )


PROTO_MSGS = """
    import dataclasses

    class Message:
        pass

    @dataclasses.dataclass
    class Ping(Message):
        node_id: int = 0

    @dataclasses.dataclass
    class Drain(Message):
        token: str = ""

    @dataclasses.dataclass
    class Lost(Message):
        node_id: int = 0
"""

PROTO_SERVICER = """
    from common import messages as m

    class Servicer:
        def __init__(self, diag=None, kv=None):
            self.diag = diag
            self.kv = kv
            self._dispatch = {
                m.Ping: self._on_ping,
                m.Drain: self._on_drain,
            }

        def _on_ping(self, msg):
            return self.diag.pop_actions(msg.node_id)

        def _on_drain(self, msg):
            self.kv.consume(msg.token)
            return None
"""

PROTO_CLIENT = """
    from common import messages as m

    class Client:
        def ping(self):
            return self._c.call(m.Ping(node_id=1), idempotent=True)

        def drain(self):
            return self._c.call(m.Drain(token="t"), idempotent=True)

        def lost(self):
            return self._c.call(m.Lost(node_id=2))
"""


class TestRpcContractRules:
    def test_pc401_sent_but_unhandled(self):
        got = proj_rules({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        })
        assert "PC401" in got
        findings = proj_findings({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        })
        (f,) = [x for x in findings if x.rule == "PC401"]
        assert f.path == "client.py" and "Lost" in f.message

    def test_pc401_negative_isinstance_handler_counts(self):
        handler = """
            from common import messages as m

            class Server:
                def handle(self, msg):
                    if isinstance(msg, m.Lost):
                        return None
                    return None
        """
        got = proj_rules({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
            "server2.py": handler,
        })
        assert "PC401" not in got

    def test_pc402_dispatch_key_not_a_message(self):
        servicer = """
            from common import messages as m

            class Servicer:
                def __init__(self):
                    self._dispatch = {
                        m.Ping: self._on_ping,
                        m.Bogus: self._on_bogus,
                    }

                def _on_ping(self, msg):
                    return None

                def _on_bogus(self, msg):
                    return None
        """
        got = proj_rules({
            "messages.py": PROTO_MSGS,
            "servicer.py": servicer,
        })
        assert "PC402" in got

    def test_pc403_destructive_idempotent_retry_flagged(self):
        """The Heartbeat bug class: idempotent=True + a handler that
        pops state without reading any token field."""
        findings = proj_findings({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        })
        pc403 = [f for f in findings if f.rule == "PC403"]
        assert len(pc403) == 1
        assert pc403[0].path == "client.py"
        assert "Ping" in pc403[0].message  # Drain consumes msg.token

    def test_pc403_negative_token_consuming_handler(self):
        # Drain's handler reads msg.token -> exempt even though its
        # manager call might be destructive.
        findings = proj_findings({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        })
        assert not any(
            f.rule == "PC403" and "Drain" in f.message
            for f in findings
        )

    def test_pc403_negative_overwrite_is_not_destructive(self):
        servicer = """
            from common import messages as m

            class Servicer:
                def __init__(self, kv=None):
                    self.kv = kv
                    self._dispatch = {
                        m.Ping: self._on_ping,
                        m.Drain: self._on_drain,
                    }

                def _on_ping(self, msg):
                    self.kv.set("a", msg.node_id)
                    return None

                def _on_drain(self, msg):
                    return None
        """
        got = proj_rules({
            "messages.py": PROTO_MSGS,
            "servicer.py": servicer,
            "client.py": PROTO_CLIENT,
        })
        assert "PC403" not in got

    def test_pc403_suppressible_at_the_call_site(self):
        client = PROTO_CLIENT.replace(
            'return self._c.call(m.Ping(node_id=1), idempotent=True)',
            'return self._c.call(m.Ping(node_id=1), idempotent=True)'
            '  # graftcheck: disable=PC403 -- delivery is at-most-once'
            ' by design',
        )
        findings = proj_findings({
            "messages.py": PROTO_MSGS,
            "servicer.py": PROTO_SERVICER,
            "client.py": client,
        })
        pc403 = [f for f in findings if f.rule == "PC403"]
        assert len(pc403) == 1 and pc403[0].suppressed
        assert "at-most-once" in pc403[0].justification


J_STATE = """
    class JournalBound:
        _journal = None

        def bind_journal(self, journal):
            self._journal = journal

        def _jrec(self, kind, **fields):
            if self._journal is not None:
                self._journal.append(kind, fields)
"""

J_MGRS = """
    from state import JournalBound

    class KV(JournalBound):
        def __init__(self):
            self._kv = {}

        def set(self, k, v):
            self._kv[k] = v
            self._jrec("kv.set", k=k)

    class Sync(JournalBound):
        def __init__(self):
            self._members = set()

        def join(self, n):
            self._members.add(n)
"""

J_SERVICER = """
    from common import messages as m

    class Servicer:
        def __init__(self, kv=None, sync=None):
            self.kv = kv
            self.sync = sync
            self._dispatch = {
                m.Ping: self._on_set,
                m.Drain: self._on_join,
            }

        def _on_set(self, msg):
            self.kv.set("a", 1)
            return None

        def _on_join(self, msg):
            self.sync.join(msg.node_id)
            return None
"""

J_MASTER = """
    from mgr import KV, Sync
    from servicer import Servicer

    class Master:
        def __init__(self):
            self.kv = KV()
            self.sync = Sync()
            self.servicer = Servicer(kv=self.kv, sync=self.sync)
"""


class TestJournalBeforeAckRule:
    FILES = {
        "messages.py": PROTO_MSGS,
        "state.py": J_STATE,
        "mgr.py": J_MGRS,
        "servicer.py": J_SERVICER,
        "master.py": J_MASTER,
    }

    def test_pc404_unjournaled_mutation_flagged(self):
        findings = proj_findings(self.FILES)
        pc404 = [f for f in findings if f.rule == "PC404"]
        assert len(pc404) == 1
        assert pc404[0].path == "mgr.py"
        assert "Sync.join" in pc404[0].message

    def test_pc404_negative_once_journaled(self):
        mgrs = J_MGRS.replace(
            "self._members.add(n)",
            'self._members.add(n)\n'
            '            self._jrec("sync.join", n=n)',
        )
        files = dict(self.FILES, **{"mgr.py": mgrs})
        assert "PC404" not in proj_rules(files)

    def test_pc404_direct_journal_append_counts(self):
        mgrs = J_MGRS.replace(
            "self._members.add(n)",
            'self._members.add(n)\n'
            '            if self._journal is not None:\n'
            '                self._journal.append("sync.join", '
            '{"n": n})',
        )
        files = dict(self.FILES, **{"mgr.py": mgrs})
        assert "PC404" not in proj_rules(files)

    def test_pc404_silent_on_unjournaled_planes(self):
        # A servicer none of whose managers journals (a gateway) has
        # its own durability story — no findings.
        mgrs = """
            class KV:
                def __init__(self):
                    self._kv = {}

                def set(self, k, v):
                    self._kv[k] = v

            class Sync:
                def __init__(self):
                    self._members = set()

                def join(self, n):
                    self._members.add(n)
        """
        master = J_MASTER.replace("from mgr import KV, Sync",
                                  "from mgr import KV, Sync")
        files = {
            "messages.py": PROTO_MSGS,
            "state.py": J_STATE,  # the mechanism exists in the model
            "mgr.py": mgrs,
            "servicer.py": J_SERVICER,
            "master.py": master,
        }
        assert "PC404" not in proj_rules(files)


class TestOrphanMessageRule:
    def test_pc405_orphan_flagged(self):
        msgs = PROTO_MSGS + """
    @dataclasses.dataclass
    class Forgotten(Message):
        pass
"""
        findings = proj_findings({
            "messages.py": msgs,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        })
        pc405 = [f for f in findings if f.rule == "PC405"]
        assert len(pc405) == 1 and "Forgotten" in pc405[0].message

    def test_pc405_negative_when_tests_reference_it(self):
        msgs = PROTO_MSGS + """
    @dataclasses.dataclass
    class ProbeOnly(Message):
        pass
"""
        got = proj_rules({
            "messages.py": msgs,
            "servicer.py": PROTO_SERVICER,
            "client.py": PROTO_CLIENT,
        }, test_text="cli.call(m.ProbeOnly())")
        assert "PC405" not in got


class TestLockOrderRules:
    def test_lk201_opposite_order_cycle(self):
        assert "LK201" in rules_of("""
            import threading

            class AB:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.x = 0

                def fwd(self):
                    with self._a:
                        with self._b:
                            self.x = 1

                def rev(self):
                    with self._b:
                        with self._a:
                            self.x = 2
        """)

    def test_lk201_negative_consistent_order(self):
        assert "LK201" not in rules_of("""
            import threading

            class AB:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.x = 0

                def fwd(self):
                    with self._a:
                        with self._b:
                            self.x = 1

                def fwd2(self):
                    with self._a:
                        with self._b:
                            self.x = 2
        """)

    def test_lk201_self_deadlock_through_call(self):
        assert "LK201" in rules_of("""
            import threading

            class S:
                def __init__(self):
                    self._mu = threading.Lock()

                def outer(self):
                    with self._mu:
                        self._inner_step()

                def _inner_step(self):
                    with self._mu:
                        pass
        """)

    def test_lk201_negative_rlock_reentry(self):
        # The Histogram _roll_locked pattern: RLock re-entry is the
        # documented idiom, not a deadlock.
        assert "LK201" not in rules_of("""
            import threading

            class H:
                def __init__(self):
                    self._lock = threading.RLock()

                def observe(self):
                    with self._lock:
                        self._roll_locked()

                def _roll_locked(self):
                    with self._lock:
                        pass
        """)

    def test_lk201_cross_class_cycle_via_typed_attr(self):
        assert "LK201" in rules_of("""
            import threading

            class Store:
                def __init__(self):
                    self._mu = threading.Lock()
                    self.peer = Peer()

                def put(self):
                    with self._mu:
                        self.peer.poke()

            class Peer:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.store = Store()

                def poke(self):
                    with self._lock:
                        pass

                def write(self):
                    with self._lock:
                        self.store.put()
        """)

    def test_lk202_locked_method_called_bare(self):
        findings = check_source(textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def _bump_locked(self):
                    self.n += 1

                def good(self):
                    with self._lock:
                        self._bump_locked()

                def bad(self):
                    self._bump_locked()
        """))
        lk = [f for f in findings if f.rule == "LK202"]
        assert len(lk) == 1
        assert "bad" in lk[0].message

    def test_lk202_negative_from_another_locked_method(self):
        assert "LK202" not in rules_of("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def _outer_locked(self):
                    self._bump_locked()

                def _bump_locked(self):
                    self.n += 1
        """)


CH_PLAN = """
    SITES = {
        "svc.flap": {"kind": "flag", "doc": "drops one call"},
        "svc.dead": {
            "kind": "crash", "exit": 9, "times": 1, "doc": "kill",
        },
    }
"""

CH_USER = """
    from chaos import inject

    def work():
        inject("svc.flap")
        inject("svc.ghost")
"""


class TestChaosCoverageRules:
    def test_ch501_declared_never_injected(self):
        findings = proj_findings({
            "chaos/plan.py": CH_PLAN,
            "svc.py": CH_USER,
        })
        ch = [f for f in findings if f.rule == "CH501"]
        assert len(ch) == 1 and "svc.dead" in ch[0].message

    def test_ch501_negative_literal_reference_elsewhere(self):
        scrubber = """
            CRASH_SITES = ("svc.dead",)
        """
        got = proj_rules({
            "chaos/plan.py": CH_PLAN,
            "svc.py": CH_USER,
            "scrub.py": scrubber,
        })
        assert "CH501" not in got

    def test_ch502_injected_but_undeclared(self):
        findings = proj_findings({
            "chaos/plan.py": CH_PLAN,
            "svc.py": CH_USER,
        })
        ch = [f for f in findings if f.rule == "CH502"]
        assert len(ch) == 1 and "svc.ghost" in ch[0].message
        assert ch[0].path == "svc.py"

    def test_ch503_needs_test_reference(self):
        scrub = 'CRASH_SITES = ("svc.dead",)\n'
        with_tests = proj_rules({
            "chaos/plan.py": CH_PLAN,
            "svc.py": CH_USER,
            "scrub.py": scrub,
        }, test_text='configure("svc.flap:p=1");  # svc.dead too')
        assert "CH503" not in with_tests
        without = proj_findings({
            "chaos/plan.py": CH_PLAN,
            "svc.py": CH_USER,
            "scrub.py": scrub,
        }, test_text='configure("svc.flap:p=1")')
        ch = [f for f in without if f.rule == "CH503"]
        assert len(ch) == 1 and "svc.dead" in ch[0].message

    def test_ch_rules_silent_without_sites_declaration(self):
        assert proj_rules({"svc.py": CH_USER}) == set()


class TestMetricsDriftRules:
    MT_SRC = """
        class Core:
            def work(self, k):
                self.counters.inc("good")
                self.counters.inc("lost")
                self.counters.inc(
                    {"a": "routed_a", "b": "routed_b"}[k]
                )

            def register_gauges(self, registry):
                for name in ("good", "routed_a", "routed_b"):
                    registry.gauge(f"s_{name}", lambda: 0.0)
    """

    def test_mt601_unexported_counter_flagged(self):
        findings = check_source(textwrap.dedent(self.MT_SRC))
        mt = [f for f in findings if f.rule == "MT601"]
        assert len(mt) == 1 and "'lost'" in mt[0].message

    def test_mt601_loop_and_dict_literal_names_resolve(self):
        # good / routed_a / routed_b are exported via the f-string
        # loop; only 'lost' fires (the dict-subscript inc resolved).
        findings = check_source(textwrap.dedent(self.MT_SRC))
        flagged = {f.message.split("'")[1]
                   for f in findings if f.rule == "MT601"}
        assert flagged == {"lost"}

    def test_mt601_silent_without_any_registration(self):
        assert "MT601" not in rules_of("""
            class Core:
                def work(self):
                    self.counters.inc("orphan")
        """)

    def test_mt602_double_registration_same_module(self):
        findings = check_source(textwrap.dedent("""
            class A:
                def register(self, registry):
                    registry.gauge("depth", lambda: 0.0)

            class B:
                def register(self, registry):
                    registry.gauge("depth", lambda: 1.0)
        """))
        mt = [f for f in findings if f.rule == "MT602"]
        assert len(mt) == 1 and "'depth'" in mt[0].message

    def test_mt602_negative_single_site(self):
        assert "MT602" not in rules_of("""
            class A:
                def register(self, registry):
                    registry.gauge("depth", lambda: 0.0)
                    registry.gauge("width", lambda: 0.0)
        """)


class TestStaleSuppression:
    def test_gc001_stale_suppression_flagged(self):
        findings = check_source(textwrap.dedent("""
            # graftcheck: disable=CC104 -- was needed before the retry
            x = 1
        """))
        (f,) = findings
        assert f.rule == "GC001" and "CC104" in f.message
        assert not f.suppressed

    def test_gc001_negative_live_suppression(self):
        findings = check_source(textwrap.dedent("""
            try:
                x = 1
            # graftcheck: disable=CC104 -- teardown must not raise
            except Exception:
                pass
        """))
        assert not any(f.rule == "GC001" for f in findings)
        assert all(f.suppressed for f in findings)

    def test_gc001_cannot_be_suppressed(self):
        findings = check_source(
            "x = 1  # graftcheck: disable=GC001 -- trying to hide\n"
        )
        gc = [f for f in findings if f.rule == "GC001"]
        assert len(gc) == 1 and not gc[0].suppressed

    def test_gc001_one_stale_one_live_on_same_comment(self):
        findings = check_source(textwrap.dedent("""
            try:
                x = 1
            # graftcheck: disable=CC104,CC102 -- only CC104 is real
            except Exception:
                pass
        """))
        rules = {(f.rule, f.suppressed) for f in findings}
        assert ("CC104", True) in rules
        assert ("GC001", False) in rules  # the CC102 half is stale


class TestChangedMode:
    """--changed: git-diff-scoped reporting over a repo-wide model."""

    def _mk_repo(self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "messages.py").write_text(textwrap.dedent("""
            import dataclasses

            class Message:
                pass

            @dataclasses.dataclass
            class Ping(Message):
                node_id: int = 0
        """))
        (pkg / "client.py").write_text(textwrap.dedent("""
            from pkg import messages as m

            class Client:
                def go(self):
                    return self._c.call(m.Ping(node_id=1))
        """))
        git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
        subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
        subprocess.run(["git", "add", "."], cwd=tmp_path, check=True)
        subprocess.run(git + ["commit", "-qm", "seed"], cwd=tmp_path,
                       check=True)
        return pkg

    def _cli(self, tmp_path, *extra):
        env = dict(os.environ, PYTHONPATH=REPO)
        return subprocess.run(
            [sys.executable, "-m", "tools.graftcheck", "pkg",
             "--changed", "HEAD", "--format", "json", *extra],
            capture_output=True, text=True, cwd=tmp_path, env=env,
        )

    def test_cross_module_finding_reported_for_changed_file(
            self, tmp_path):
        pkg = self._mk_repo(tmp_path)
        with open(pkg / "client.py", "a") as fh:
            fh.write("# touched\n")
        r = self._cli(tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        blob = json.loads(r.stdout)
        rules = {(f["rule"], f["path"]) for f in blob["findings"]}
        # PC401 anchors in client.py (the changed file) even though
        # the evidence (no handler) spans the whole model.
        assert ("PC401", os.path.join("pkg", "client.py")) in rules

    def test_findings_outside_the_diff_are_filtered(self, tmp_path):
        pkg = self._mk_repo(tmp_path)
        with open(pkg / "messages.py", "a") as fh:
            fh.write("# touched\n")
        r = self._cli(tmp_path)
        # The PC401 is anchored in client.py, which did NOT change.
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(r.stdout)["unsuppressed"] == 0

    def test_clean_diff_exits_zero_fast(self, tmp_path):
        self._mk_repo(tmp_path)
        r = self._cli(tmp_path)
        assert r.returncode == 0
        assert "no changed" in r.stdout

    def test_one_file_changed_run_under_five_seconds(self):
        """The acceptance bound: model built repo-wide, one target
        file, < 5s — the pre-commit loop's budget.  CPU seconds of this
        process (the run is one thread): under six workers the host's
        clock counts the other five's load too, 5.02 s once (PR 62)."""
        import time as _time

        t0 = _time.process_time()
        findings, _model = run_project(
            [os.path.join(REPO, "dlrover_tpu")],
            targets=[os.path.join(
                REPO, "dlrover_tpu", "serving", "gateway.py"
            )],
        )
        elapsed = _time.process_time() - t0
        assert elapsed < 5.0, f"--changed-style run took {elapsed:.1f}s"
        assert not [f for f in findings if not f.suppressed]


@pytest.mark.graftcheck
class TestChaosTableDrift:
    """--chaos-table: the README's injection-point catalog is GENERATED
    from chaos/plan.py's SITES (docs cannot drift from the code)."""

    @pytest.fixture(scope="class")
    def repo_model(self):
        _findings, model = run_project(
            [os.path.join(REPO, "dlrover_tpu")]
        )
        return model

    def test_readme_table_matches_generated(self, repo_model):
        table = render_chaos_table(repo_model)
        with open(os.path.join(REPO, "README.md"),
                  encoding="utf-8") as fh:
            readme = fh.read()
        begin = "<!-- graftcheck:chaos-table:begin -->"
        end = "<!-- graftcheck:chaos-table:end -->"
        assert begin in readme and end in readme, (
            "README chaos-table markers missing"
        )
        block = readme.split(begin, 1)[1].split(end, 1)[0]
        embedded = "\n".join(
            line for line in block.splitlines()
            if line.startswith("|")
        )
        assert embedded.strip() == table.strip(), (
            "README chaos table drifted from chaos/plan.py — "
            "regenerate with `python -m tools.graftcheck dlrover_tpu "
            "--chaos-table`"
        )

    def test_every_site_has_a_doc_and_a_row(self, repo_model):
        table = render_chaos_table(repo_model)
        from dlrover_tpu.chaos.plan import SITES

        assert set(repo_model.chaos_sites) == set(SITES)
        for site, decl in repo_model.chaos_sites.items():
            assert f"`{site}`" in table
            assert decl.doc, f"SITES[{site!r}] has no doc string"


@pytest.mark.graftcheck
def test_subdirectory_invocation_uses_the_full_model():
    """Regression: a subdirectory run must expand the model to the
    whole tree — a partial model made cross-module rules stop firing
    and GC001 then flagged the full gate's REQUIRED suppressions as
    stale (following that finding would break the repo gate)."""
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftcheck",
         "dlrover_tpu/agent"],
        capture_output=True, text=True, cwd=REPO, env=env,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GC001" not in r.stdout


class TestSuppressionTokenization:
    """Suppression directives must be real COMMENT tokens: the syntax
    QUOTED in a docstring/string is documentation, and treating it as
    live made the (unsuppressible) GC001 flag the tool's own docs."""

    def test_docstring_example_is_not_a_suppression(self):
        findings = check_source(textwrap.dedent('''
            """Usage:

            ``# graftcheck: disable=JX003 -- memoized, compiled once``
            """
        '''))
        assert findings == []

    def test_string_literal_suppression_does_not_suppress(self):
        findings = check_source(textwrap.dedent("""
            DOC = "# graftcheck: disable=CC104 -- quoted example"
            try:
                x = 1
            except Exception:
                pass
        """))
        cc = [f for f in findings if f.rule == "CC104"]
        assert len(cc) == 1 and not cc[0].suppressed
        assert not any(f.rule == "GC001" for f in findings)

    def test_real_comment_after_string_still_counts(self):
        findings = check_source(textwrap.dedent("""
            try:
                s = "#not a comment"
            except Exception:  # graftcheck: disable=CC104 -- teardown
                pass
        """))
        assert all(f.suppressed for f in findings)


class TestChangedModePathResolution:
    """Review regressions: --changed must survive absolute paths,
    non-root cwds, and must SEE untracked files."""

    def test_changed_files_are_absolute_and_include_untracked(
            self, tmp_path):
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "a.py").write_text("x = 1\n")
        git = ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
        subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
        subprocess.run(["git", "add", "."], cwd=tmp_path, check=True)
        subprocess.run(git + ["commit", "-qm", "seed"], cwd=tmp_path,
                       check=True)
        (pkg / "a.py").write_text("x = 2\n")        # tracked change
        (pkg / "new.py").write_text("y = 1\n")      # untracked
        from tools.graftcheck.engine import changed_files

        got = changed_files("HEAD", cwd=str(tmp_path))
        assert all(os.path.isabs(p) for p in got)
        names = {os.path.basename(p) for p in got}
        assert names == {"a.py", "new.py"}
        # And from a SUBDIRECTORY cwd the same set resolves.
        got2 = changed_files("HEAD", cwd=str(pkg))
        assert {os.path.basename(p) for p in got2} == names

    def test_find_model_root_from_analyzed_path_not_cwd(self):
        from tools.graftcheck.engine import find_model_root

        root = find_model_root(
            [os.path.join(REPO, "dlrover_tpu", "common",
                          "messages.py")]
        )
        assert root == os.path.join(REPO, "dlrover_tpu")

    def test_single_file_from_foreign_cwd_gets_full_model(
            self, tmp_path):
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck",
             os.path.join(REPO, "dlrover_tpu", "common",
                          "messages.py")],
            capture_output=True, text=True, cwd=tmp_path, env=env,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "PC405" not in r.stdout


@pytest.mark.graftcheck
class TestCellSurfaceModeled:
    """ISSUE 15 satellite: the multi-cell protocol surface is IN the
    project model from day one, so PC4xx (contracts, journal-before-
    ack), CH5xx (chaos drift) and MT6xx (dark counters) cover it — a
    refactor that drops the cell messages, handlers, sites or gauges
    out of the model would silently exempt them from every rule."""

    @pytest.fixture(scope="class")
    def repo_model(self):
        _findings, model = run_project(
            [os.path.join(REPO, "dlrover_tpu")]
        )
        return model

    def test_cell_messages_and_handlers_modeled(self, repo_model):
        msgs = set(repo_model.messages)
        assert {"CellSnapshotRequest", "CellSnapshot",
                "CellPlacementUpdate"} <= msgs
        handled = repo_model.handled_messages()
        assert "CellSnapshotRequest" in handled
        assert "CellPlacementUpdate" in handled

    def test_cell_chaos_sites_declared_and_injected(self, repo_model):
        assert {"cell.master_kill", "cell.split"} <= set(
            repo_model.chaos_sites
        )
        injected = {i.name for i in repo_model.injects}
        assert {"cell.master_kill", "cell.split"} <= injected

    def test_placement_handler_reaches_journal(self, repo_model):
        # The PC404 obligation is LIVE on the new surface: the
        # placement mutation journals before the servicer acks.
        assert repo_model.method_reaches_jrec(
            "CellManager", "apply_placement"
        )

    def test_federation_counters_all_exported(self, repo_model):
        from dlrover_tpu.cells.federation import (
            FEDERATION_COUNTER_NAMES,
        )

        incs = {c.name for c in repo_model.counter_incs}
        gauges = {str(g.name) for g in repo_model.gauge_regs}
        for name in FEDERATION_COUNTER_NAMES:
            assert name in incs
            assert f"fed_{name}" in gauges


# ---------------------------------------------------------------------------
# v3: effect inference + the DET determinism families (ISSUE 16)
# ---------------------------------------------------------------------------


def det_rules_of(sources):
    """Unsuppressed rule ids over a multi-file fixture whose virtual
    paths resolve against the pure-policy registry."""
    return {
        f.rule
        for f in check_project({
            p: textwrap.dedent(s) for p, s in sources.items()
        })
        if not f.suppressed
    }


class TestEffectRules:
    """DET701-705: every family fires on a fixture (the families-live
    half of the tier-1 gate) and stays silent on the seamed form."""

    def test_det701_ambient_clock_in_registered_policy(self):
        assert "DET701" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                import time
                def decide(snapshot, policy, state):
                    return int(time.time()) % 4
            """,
        })

    def test_det701_transitive_through_module_helper(self):
        # The policy itself is clean; the ambient read hides one call
        # away — the transitive closure still charges it.
        assert "DET701" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                import time
                def _now_bucket():
                    return int(time.monotonic())
                def decide(snapshot, policy, state):
                    return _now_bucket() % 4
            """,
        })

    def test_det701_seam_bypass_in_seamed_class(self):
        # Not registered, but the class HAS a clock seam: bypassing it
        # fires even outside the registry.
        assert "DET701" in rules_of("""
            import time
            class Sweeper:
                def __init__(self, clock=time.monotonic):
                    self._clock = clock
                def sweep(self):
                    return time.monotonic()
        """)

    def test_det701_silent_behind_the_seam(self):
        assert "DET701" not in det_rules_of({
            "dlrover_tpu/serving/gateway.py": """
                import time
                class GatewayCore:
                    def __init__(self, clock=time.monotonic):
                        self._clock = clock
                    def sweep(self):
                        return self._clock()
            """,
        })

    def test_det702_unseeded_randomness(self):
        assert "DET702" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                import random
                def decide(snapshot, policy, state):
                    return random.randint(0, 4)
            """,
        })

    def test_det703_thread_spawn_and_blocking_io(self):
        assert "DET703" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                import threading
                def decide(snapshot, policy, state):
                    threading.Thread(target=print).start()
                    return 1
            """,
        })
        assert "DET703" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                import time
                def decide(snapshot, policy, state):
                    time.sleep(0.1)
                    return 1
            """,
        })

    def test_det704_set_iteration_picks_in_hash_order(self):
        assert "DET704" in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                def decide(snapshot, policy, state):
                    victims = set(snapshot)
                    for v in victims:
                        return v
            """,
        })

    def test_det704_sorted_iteration_is_a_total_order(self):
        assert "DET704" not in det_rules_of({
            "dlrover_tpu/serving/autoscale.py": """
                def decide(snapshot, policy, state):
                    victims = set(snapshot)
                    for v in sorted(victims):
                        return v
            """,
        })

    def test_det704_class_policy_method_surface(self):
        assert "DET704" in det_rules_of({
            "dlrover_tpu/common/hashring.py": """
                class HashRing:
                    def __init__(self, members):
                        self._members = set(members)
                    def owner(self, key):
                        return next(iter(self._members))
            """,
        })

    def test_det705_wall_stamp_into_audit_state(self):
        assert "DET705" in rules_of("""
            import time
            class Actuator:
                def __init__(self):
                    self.decisions = []
                def scale_once(self, alive, target):
                    self.decisions.append((time.time(), alive, target))
        """)

    def test_det705_silent_through_injected_clock(self):
        assert "DET705" not in rules_of("""
            import time
            class Actuator:
                def __init__(self, clock=time.time):
                    self._clock = clock
                    self.decisions = []
                def scale_once(self, alive, target):
                    self.decisions.append((self._clock(), alive, target))
        """)

    def test_det_suppression_honoured_with_justification(self):
        findings = check_source(textwrap.dedent("""
            import time
            class Actuator:
                def __init__(self):
                    self.decisions = []
                def scale_once(self, alive, target):
                    self.decisions.append((time.time(), alive, target))  # graftcheck: disable=DET705 -- operator-facing audit log, never replayed
        """))
        det = [f for f in findings if f.rule == "DET705"]
        assert det and all(f.suppressed for f in det)
        assert "never replayed" in det[0].justification


class TestPolicyRegistry:
    """The sim-bound object registry: non-vacuous, and every entry
    resolves against the real tree."""

    def test_registry_covers_at_least_eight_objects(self):
        from tools.graftcheck.policy_registry import REGISTRY

        assert len(REGISTRY) >= 8
        assert len({p.label for p in REGISTRY}) == len(REGISTRY)
        for p in REGISTRY:
            assert p.kind in ("class", "function"), p.label
            assert p.doc.strip(), p.label

    def test_named_tentpole_policies_are_registered(self):
        from tools.graftcheck.policy_registry import REGISTRY

        names = {p.name for p in REGISTRY}
        assert {"GatewayCore", "decide", "decide_pools", "HashRing",
                "merge_cell_snapshots", "place_roles", "detect_splits",
                "ChipBorrowArbiter", "build_plan",
                "plan_persist"} <= names


@pytest.mark.graftcheck
class TestEffectsManifest:
    """--effects + the committed POLICY_EFFECTS.json drift gate:
    effect drift on any registered policy fails tier-1."""

    @pytest.fixture(scope="class")
    def manifest(self):
        from tools.graftcheck.effect_rules import effects_manifest

        _findings, model = run_project(
            [os.path.join(REPO, "dlrover_tpu")]
        )
        return effects_manifest(model)

    def test_schema_and_resolution(self, manifest):
        from tools.graftcheck.effects import EFFECT_KINDS

        assert manifest["schema"] == "graftcheck.policy_effects.v1"
        assert len(manifest["policies"]) >= 8
        for label, entry in manifest["policies"].items():
            assert entry["kind"] in ("class", "function"), label
            assert entry["resolved"], f"{label} does not resolve"
            assert set(entry["ambient_effects"]) <= set(EFFECT_KINDS)

    def test_registered_policies_have_empty_effect_sets(
            self, manifest):
        dirty = {
            label: entry["ambient_effects"]
            for label, entry in manifest["policies"].items()
            if entry["ambient_effects"]
        }
        assert not dirty, (
            f"registered policies grew ambient effects: {dirty}"
        )

    def test_committed_manifest_matches_generated(self, manifest):
        with open(os.path.join(REPO, "POLICY_EFFECTS.json"),
                  encoding="utf-8") as fh:
            committed = json.load(fh)
        assert committed == manifest, (
            "POLICY_EFFECTS.json drifted — regenerate with "
            "`python -m graftcheck --effects dlrover_tpu/`"
        )

    def test_effects_cli_emits_the_manifest(self, manifest):
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftcheck", "--effects",
             "dlrover_tpu"],
            capture_output=True, text=True, cwd=REPO, env=env,
        )
        assert r.returncode == 0, r.stderr
        assert json.loads(r.stdout) == manifest
