"""bigdl_tpu.analysis: the project-specific static checker suite.

Per checker: a demonstrated TRUE POSITIVE (the documented bug class,
e.g. the PR 15 use-after-donate pattern), a negative (the in-tree safe
idiom must NOT flag), and the escape-hatch path. Plus the baseline
round-trip, the lint_cli exit-code contract, the strict-telemetry
runtime twin, the `--lint-stream` gate, and the acceptance test: the
shipped tree (package + scripts/, deep checks included) has ZERO
non-baselined findings — the state `scripts/run_ci.sh --lint` gates.
"""

import json
import os
import textwrap

import pytest

from bigdl_tpu.analysis import (DonationChecker, FaultSiteChecker,
                                LockChecker, RecompileChecker,
                                TelemetryChecker, TilingChecker,
                                apply_baseline, default_baseline_path,
                                default_checkers, load_baseline,
                                run_checkers, save_baseline)
from bigdl_tpu.analysis.core import SourceFile
from bigdl_tpu.tools import lint_cli, metrics_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_on(checker, code, name="bigdl_tpu/serving/fixture.py"):
    """Run one checker over one in-memory module."""
    src = SourceFile(name, textwrap.dedent(code))
    assert src.parse_error is None, src.parse_error
    checker.begin([src])
    return checker.check(src) + checker.finalize()


def rules(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------- #
# donation safety
# --------------------------------------------------------------------- #

class TestDonation:
    def test_use_after_donate_true_positive(self):
        # the PR 15 bug class: a donated binding read after the call
        # that deleted its buffers
        f = run_on(DonationChecker(), """
            import jax
            def train(params, opt, x):
                step = jax.jit(fn, donate_argnums=(0, 1))
                new_p, new_o = step(params, opt, x)
                return params["w"]
        """)
        assert rules(f) == ["use-after-donate"]
        assert f[0].line == 6  # file:line points at the stale read
        assert "params" in f[0].message

    def test_same_statement_rebind_is_safe(self):
        # the in-tree optimizer loop idiom (optim/local_optimizer.py):
        # donated args rebound by the call's own assignment targets
        f = run_on(DonationChecker(), """
            import jax
            def train(params, opt, xs):
                step = jax.jit(fn, donate_argnums=(0, 1))
                for x in xs:
                    params, opt = step(params, opt, x)
                return params
        """)
        assert f == []

    def test_store_before_read_is_safe(self):
        # model_state = new_ms before any read: the donated name is
        # rebound before use (the local_optimizer loop tail)
        f = run_on(DonationChecker(), """
            import jax
            def train(ms, x):
                step = jax.jit(fn, donate_argnums=(0,))
                new_ms, loss = step(ms, x)
                ms = new_ms
                return ms, loss
        """)
        assert f == []

    def test_self_alias_true_positive(self):
        # a donated arg aliasing a field retained on self: the buffer
        # self.params points at is deleted by the call
        f = run_on(DonationChecker(), """
            import jax
            class Opt:
                def __init__(self):
                    self.step = jax.jit(fn, donate_argnums=(0,))
                def go(self, x):
                    return self.step(self.params, x)
        """)
        assert rules(f) == ["self-alias"]

    def test_self_alias_rebound_in_statement_is_safe(self):
        f = run_on(DonationChecker(), """
            import jax
            class Opt:
                def __init__(self):
                    self.step = jax.jit(fn, donate_argnums=(0,))
                def go(self, x):
                    self.params, aux = self.step(self.params, x)
                    return aux
        """)
        assert f == []

    def test_compiledfunction_donation_tracked(self):
        f = run_on(DonationChecker(), """
            from bigdl_tpu.observability.compilation import CompiledFunction
            def train(params, x):
                step = CompiledFunction(fn, label="s", donate_argnums=(0,))
                out = step(params, x)
                return params
        """)
        assert rules(f) == ["use-after-donate"]

    def test_escape_hatch(self):
        f = run_on(DonationChecker(), """
            import jax
            def train(params, x):
                step = jax.jit(fn, donate_argnums=(0,))
                out = step(params, x)
                return params  # lint: donation-ok(interpreter mode: donation is a no-op here)
        """)
        assert f == []


# --------------------------------------------------------------------- #
# lock discipline
# --------------------------------------------------------------------- #

LOCK_FIXTURE = """
    class S:
        def __init__(self):
            self._n = 0          # __init__ is exempt
        def bump(self):
            with self._lock:
                self._n += 1
        def peek(self):
            return self._n       # TP: unguarded read
        def reset(self):
            self._n = 0          # TP: unguarded write
        def safe(self):
            with self._lock:
                return self._n
        def _snap_unlocked(self):
            return self._n       # caller-holds-the-lock convention
"""


class TestLocks:
    def test_true_positives_and_exemptions(self):
        f = run_on(LockChecker(all_files=True), LOCK_FIXTURE)
        assert sorted(rules(f)) == ["unguarded-read", "unguarded-write"]
        by_rule = {x.rule: x for x in f}
        assert "peek" in by_rule["unguarded-read"].message
        assert "reset" in by_rule["unguarded-write"].message

    def test_unlocked_suffix_writes_feed_guarded_set(self):
        # a *_unlocked method's writes count as lock-held: the field it
        # mutates becomes guarded, so an unguarded read elsewhere flags
        f = run_on(LockChecker(all_files=True), """
            class S:
                def go(self):
                    with self._lock:
                        self._apply_unlocked()
                def _apply_unlocked(self):
                    self._state = 1
                def peek(self):
                    return self._state
        """)
        assert rules(f) == ["unguarded-read"]

    def test_scope_is_serving_and_resilience(self):
        f = run_on(LockChecker(), LOCK_FIXTURE,
                   name="bigdl_tpu/optim/fixture.py")
        assert f == []

    def test_escape_hatch_with_reason(self):
        f = run_on(LockChecker(all_files=True), """
            class S:
                def bump(self):
                    with self._lock:
                        self._n += 1
                def peek(self):
                    return self._n  # lint: unguarded-ok(monotonic gauge; stale read is fine)
        """)
        assert f == []

    def test_escape_hatch_without_reason_is_a_finding(self):
        f = run_on(LockChecker(all_files=True), """
            class S:
                def bump(self):
                    with self._lock:
                        self._n += 1
                def peek(self):
                    return self._n  # lint: unguarded-ok
        """)
        assert rules(f) == ["escape-hatch-missing-reason"]


# --------------------------------------------------------------------- #
# recompile hazards
# --------------------------------------------------------------------- #

class TestRecompile:
    def test_jit_in_loop(self):
        f = run_on(RecompileChecker(), """
            import jax
            def hot(xs):
                for x in xs:
                    step = jax.jit(fn)
                    step(x)
        """, name="bigdl_tpu/optim/fixture.py")
        assert rules(f) == ["jit-in-loop"]

    def test_static_arg_in_loop(self):
        f = run_on(RecompileChecker(), """
            import jax
            step = jax.jit(fn, static_argnums=(1,))
            def hot(x, lengths):
                for n in lengths:
                    step(x, n)
        """, name="bigdl_tpu/optim/fixture.py")
        assert rules(f) == ["static-arg-in-loop"]

    def test_pytree_structure_and_varying_shape(self):
        f = run_on(RecompileChecker(), """
            import jax
            step = jax.jit(fn)
            def hot(x, xs):
                acc = []
                for i, v in enumerate(xs):
                    acc = acc + [v]
                    step(tuple(acc))  # growing pytree
                    step(x[:i])       # varying shape
        """, name="bigdl_tpu/serving/fixture.py")
        assert sorted(rules(f)) == ["pytree-structure", "varying-shape"]

    def test_hoisted_jit_with_stable_args_is_safe(self):
        f = run_on(RecompileChecker(), """
            import jax
            def hot(params, xs):
                step = jax.jit(fn)
                for x in xs:
                    params = step(params, x)
                return params
        """, name="bigdl_tpu/optim/fixture.py")
        assert f == []


# --------------------------------------------------------------------- #
# telemetry schema conformance
# --------------------------------------------------------------------- #

SCHEMAS = {
    "step": {"required": {"step": int}, "optional": {"loss": float}},
    "event": {"required": {"event": str}, "optional": {}, "open": True},
}


class TestTelemetrySchema:
    def test_unknown_type(self):
        f = run_on(TelemetryChecker(schemas=SCHEMAS), """
            def go(t):
                t.emit({"type": "stepp", "step": 1})
        """)
        assert rules(f) == ["unknown-type"]
        assert "stepp" in f[0].message

    def test_undeclared_and_missing(self):
        f = run_on(TelemetryChecker(schemas=SCHEMAS), """
            def go(t):
                t.emit({"type": "step", "bogus": 1})
        """)
        assert sorted(rules(f)) == ["missing-required", "undeclared-field"]

    def test_conforming_and_open_records(self):
        f = run_on(TelemetryChecker(schemas=SCHEMAS), """
            def go(t, extra):
                t.emit({"type": "step", "step": 1, "loss": 0.1})
                t.emit({"type": "event", "event": "x", "anything": 1})
                t.emit({"type": "step", "step": 1, **extra})
        """)
        assert f == []

    def test_splat_suppresses_missing_required_only(self):
        f = run_on(TelemetryChecker(schemas=SCHEMAS), """
            def go(t, extra):
                t.emit({"type": "step", "bogus": 1, **extra})
        """)
        assert rules(f) == ["undeclared-field"]

    def test_real_schemas_accept_in_tree_emit(self):
        # lazy-loaded live RECORD_SCHEMAS: the telemetry module's own
        # helper emits must conform (subset of the acceptance test)
        f = run_on(TelemetryChecker(), """
            def go(t):
                t.emit({"type": "run_end", "loss": 0.5})
        """)
        assert f == []


# --------------------------------------------------------------------- #
# fault-site resolution
# --------------------------------------------------------------------- #

class TestFaultSites:
    def test_unknown_site_with_hint(self):
        f = run_on(FaultSiteChecker(known={"mesh.device_loss"}), """
            from bigdl_tpu.resilience import faults
            def go():
                faults.fire("mesh.device_los")
        """)
        assert rules(f) == ["unknown-site"]
        assert "mesh.device_loss" in f[0].hint

    def test_register_site_resolves_cross_file(self):
        reg = SourceFile("bigdl_tpu/serving/a.py", textwrap.dedent("""
            from bigdl_tpu.resilience import faults
            SITE_X = faults.register_site("serve.x")
        """))
        use = SourceFile("bigdl_tpu/serving/b.py", textwrap.dedent("""
            from bigdl_tpu.resilience import faults
            def go():
                faults.fire("serve.x")
                faults.fire(SITE_X)
        """))
        c = FaultSiteChecker(known=set())
        c.begin([reg, use])
        assert c.check(reg) == [] and c.check(use) == []

    def test_faultspec_literal_checked(self):
        f = run_on(FaultSiteChecker(known={"a.b"}), """
            from bigdl_tpu.resilience.faults import FaultSpec
            def go():
                return [FaultSpec("a.b"), FaultSpec(site="a.typo")]
        """)
        assert rules(f) == ["unknown-site"]

    def test_bad_site_format(self):
        f = run_on(FaultSiteChecker(known=set()), """
            from bigdl_tpu.resilience import faults
            SITE = faults.register_site("nodots")
        """)
        assert rules(f) == ["bad-site-format"]

    def test_dynamic_site_and_foreign_fire_skipped(self):
        f = run_on(FaultSiteChecker(known=set()), """
            def fire(x):  # unrelated local helper (nn/dynamic_graph.py)
                return x
            def go(site):
                fire("not.a.site")
                other.fire(site)
        """)
        assert f == []


# --------------------------------------------------------------------- #
# pallas tiling
# --------------------------------------------------------------------- #

class TestTiling:
    def test_block_literal_and_unvalidated_tile(self):
        f = run_on(TilingChecker(), """
            import jax.experimental.pallas as pl
            def k(x, n, tn):
                return pl.pallas_call(body, grid=(n // tn,),
                    in_specs=[pl.BlockSpec((12, 128), lambda i: (i, 0))])(x)
        """, name="bigdl_tpu/ops/fixture.py")
        assert sorted(rules(f)) == ["block-literal", "unvalidated-tile"]

    def test_picked_and_guarded_tiles_are_safe(self):
        f = run_on(TilingChecker(), """
            import jax.experimental.pallas as pl
            def k(x, n, c, t2):
                tn = _pick_tile_n(n, c)
                assert n % t2 == 0
                pl.pallas_call(body, grid=(n // tn,),
                    in_specs=[pl.BlockSpec((tn, c), lambda i: (i, 0))])(x)
                pl.pallas_call(body, grid=(n // t2,),
                    out_specs=pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)))(x)
        """, name="bigdl_tpu/ops/fixture.py")
        assert f == []

    def test_single_row_2d_block_is_a_block_literal(self):
        # (1, c) over [n_tiles, c] is what Pallas's TPU lowering refused
        # in bn_relu's partial sums; legal only where the array's own
        # row count is 1, which the escape hatch states
        src = """
            import jax.experimental.pallas as pl
            def k(x, n, c):
                tn = _pick_tile_n(n, c)
                part = pl.BlockSpec((1, c), lambda i: (i, 0)){hatch}
                pl.pallas_call(body, grid=(n // tn,), out_specs=part)(x)
        """
        f = run_on(TilingChecker(), src.format(hatch=""),
                   name="bigdl_tpu/ops/fixture.py")
        assert rules(f) == ["block-literal"]
        f = run_on(TilingChecker(), src.format(
            hatch="  # lint: tiling-ok(the array is [1, c])"),
            name="bigdl_tpu/ops/fixture.py")
        assert f == []

    def test_deep_check_real_pickers_hold(self):
        from bigdl_tpu.analysis.tiling import deep_check
        assert deep_check() == []


# --------------------------------------------------------------------- #
# baseline round-trip + ratchet
# --------------------------------------------------------------------- #

class TestBaseline:
    def _findings(self):
        return run_on(DonationChecker(), """
            import jax
            def train(params, x):
                step = jax.jit(fn, donate_argnums=(0,))
                out = step(params, x)
                return params
        """)

    def test_round_trip_suppresses(self, tmp_path):
        f = self._findings()
        assert len(f) == 1
        path = str(tmp_path / "baseline.json")
        save_baseline(path, f, reason="fixture: documented stale read")
        new, unused = apply_baseline(self._findings(), load_baseline(path))
        assert new == [] and unused == []

    def test_unused_entries_reported(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        save_baseline(path, self._findings(), reason="r")
        new, unused = apply_baseline([], load_baseline(path))
        assert new == [] and len(unused) == 1

    def test_key_is_line_number_independent(self):
        a = self._findings()[0]
        b = run_on(DonationChecker(), """
            import jax
            # an unrelated comment shifts every line number
            def train(params, x):
                step = jax.jit(fn, donate_argnums=(0,))
                out = step(params, x)
                return params
        """)[0]
        assert a.line != b.line and a.key == b.key

    def test_reasonless_entry_rejected(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        path2 = str(tmp_path / "broken.json")
        with open(path, "w") as fh:
            json.dump({"version": 1, "findings": [
                {"key": "k", "reason": ""}]}, fh)
        with open(path2, "w") as fh:
            json.dump({"findings": "nope"}, fh)
        with pytest.raises(ValueError, match="no reason"):
            load_baseline(path)
        with pytest.raises(ValueError):
            load_baseline(path2)

    def test_missing_file_is_empty(self, tmp_path):
        assert load_baseline(str(tmp_path / "absent.json")) == {}


# --------------------------------------------------------------------- #
# lint_cli exit-code contract
# --------------------------------------------------------------------- #

BUGGY = """
import jax
def train(params, x):
    step = jax.jit(fn, donate_argnums=(0,))
    out = step(params, x)
    return params
"""


class TestLintCli:
    def test_clean_tree_exits_0(self, tmp_path, capsys):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "ok.py").write_text("x = 1\n")
        assert lint_cli.main(["check", str(d), "--baseline",
                              str(tmp_path / "b.json")]) == 0

    def test_findings_exit_1_with_json_list(self, tmp_path, capsys):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "bug.py").write_text(BUGGY)
        rc = lint_cli.main(["check", str(d), "--format", "json",
                            "--baseline", str(tmp_path / "b.json")])
        assert rc == 1
        out = json.loads(capsys.readouterr().out)
        assert out["findings"][0]["rule"] == "use-after-donate"
        assert out["findings"][0]["line"] == 6

    def test_update_baseline_then_green(self, tmp_path, capsys):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "bug.py").write_text(BUGGY)
        b = str(tmp_path / "b.json")
        assert lint_cli.main(["check", str(d), "--baseline", b,
                              "--update-baseline"]) == 0
        assert lint_cli.main(["check", str(d), "--baseline", b]) == 0

    def test_usage_and_io_errors_exit_2(self, tmp_path):
        assert lint_cli.main([]) == 2
        assert lint_cli.main(["check", "--format", "yaml"]) == 2
        assert lint_cli.main(["check", str(tmp_path / "nope")]) == 2
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "ok.py").write_text("x = 1\n")
        assert lint_cli.main(["check", str(d), "--baseline",
                              str(bad)]) == 2

    def test_parse_error_is_a_finding_not_a_crash(self, tmp_path, capsys):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "broken.py").write_text("def f(:\n")
        rc = lint_cli.main(["check", str(d), "--format", "json",
                            "--baseline", str(tmp_path / "b.json")])
        assert rc == 1
        out = json.loads(capsys.readouterr().out)
        assert out["findings"][0]["rule"] == "parse-error"


# --------------------------------------------------------------------- #
# strict telemetry (the runtime twin)
# --------------------------------------------------------------------- #

class TestStrictTelemetry:
    def test_unknown_type_raises_under_strict(self, monkeypatch):
        from bigdl_tpu.observability.telemetry import Telemetry
        monkeypatch.setenv("BIGDL_TPU_STRICT_TELEMETRY", "1")
        t = Telemetry()
        t.emit({"type": "step", "step": 1})  # declared: fine
        with pytest.raises(ValueError, match="unknown telemetry record"):
            t.emit({"type": "not_a_record"})

    def test_lenient_without_the_env(self, monkeypatch):
        from bigdl_tpu.observability.telemetry import Telemetry
        monkeypatch.delenv("BIGDL_TPU_STRICT_TELEMETRY", raising=False)
        Telemetry().emit({"type": "not_a_record"})  # tolerated


# --------------------------------------------------------------------- #
# metrics_cli report --lint-stream
# --------------------------------------------------------------------- #

class TestLintStream:
    def test_conforming_stream_exits_0(self, tmp_path, capsys):
        p = tmp_path / "run.jsonl"
        p.write_text('{"type": "step", "time": 1.0, "step": 1}\n')
        assert metrics_cli.main(["report", "--lint-stream", str(p)]) == 0
        assert "1 record" in capsys.readouterr().out

    def test_first_violation_exits_2_with_line(self, tmp_path, capsys):
        p = tmp_path / "run.jsonl"
        p.write_text('{"type": "step", "time": 1.0, "step": 1}\n'
                     '{"type": "step", "time": 2.0}\n')
        assert metrics_cli.main(["report", "--lint-stream", str(p)]) == 2
        assert f"{p}:2" in capsys.readouterr().err

    def test_empty_stream_exits_2(self, tmp_path):
        p = tmp_path / "run.jsonl"
        p.write_text("")
        assert metrics_cli.main(["report", "--lint-stream", str(p)]) == 2


# --------------------------------------------------------------------- #
# acceptance: the shipped tree is clean
# --------------------------------------------------------------------- #

class TestAcceptance:
    def test_shipped_tree_has_zero_nonbaselined_findings(self):
        from bigdl_tpu.analysis.tiling import deep_check
        findings = run_checkers(
            [os.path.join(REPO, "bigdl_tpu"),
             os.path.join(REPO, "scripts")], default_checkers())
        findings.extend(deep_check())
        baseline = load_baseline(default_baseline_path())
        new, unused = apply_baseline(findings, baseline)
        assert new == [], "\n".join(f.text() for f in new)
        assert unused == [], f"stale baseline entries: {unused}"

    def test_cli_default_surface_exits_0(self):
        assert lint_cli.main(["check"]) == 0
