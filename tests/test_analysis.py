"""graftlint (alphatriangle_tpu/analysis/, docs/ANALYSIS.md).

Every rule is pinned by one fixture true positive AND one near-miss
true negative, so the analyzer's precision is a test contract. The
engine tests pin the pragma/baseline semantics and the exit-code
contract (0 clean / 1 findings-or-stale-baseline / 2 parse error);
the CLI tests drive `cli lint` exactly as the Makefile does,
including the no-jax import guard.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from alphatriangle_tpu.analysis import (
    LINT_SCHEMA,
    RULE_NAMES,
    run_lint,
    write_baseline,
)
from alphatriangle_tpu.cli import main as cli_main

REPO = Path(__file__).resolve().parent.parent


def lint_tree(tmp_path, files, **kw):
    """Write {relpath: source} under a fresh root and lint it."""
    root = tmp_path / "pkg"
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return run_lint(root, **kw)


def rules_hit(report):
    return {f.rule for f in report.findings}


# --- rule: use-after-donation ---------------------------------------------


DONATION_BAD = """
    import jax

    class Trainer:
        def __init__(self, cache, impl):
            self._step = cache.wrap(
                "learner_step", jax.jit(impl, donate_argnums=(0,))
            )

        def bad(self, state, batch):
            new_state, metrics = self._step(state, batch)
            return state.params, metrics
"""

DONATION_GOOD = """
    import jax

    class Trainer:
        def __init__(self, cache, impl):
            self._step = cache.wrap(
                "learner_step", jax.jit(impl, donate_argnums=(0,))
            )

        def good(self, state, batch):
            state, metrics = self._step(state, batch)
            return state.params, metrics

        def also_good(self, state, batch):
            out, metrics = self._step(state, batch)
            return batch, metrics
"""


class TestUseAfterDonation:
    def test_true_positive(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/t.py": DONATION_BAD})
        hits = [f for f in r.findings if f.rule == "use-after-donation"]
        assert len(hits) == 1
        assert "`state`" in hits[0].message
        assert hits[0].context == "Trainer.bad"

    def test_true_negative_rebind_and_other_arg(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/t.py": DONATION_GOOD})
        assert "use-after-donation" not in rules_hit(r)

    def test_direct_jit_assignment(self, tmp_path):
        src = """
            import jax

            def run(buf, rows):
                ingest = jax.jit(_impl, donate_argnums=(0,))
                out = ingest(buf, rows)
                return buf.shape
        """
        r = lint_tree(tmp_path, {"rl/u.py": src})
        assert "use-after-donation" in rules_hit(r)

    def test_lambda_factory_not_treated_as_donating(self, tmp_path):
        # A factory RETURNING donating programs is not itself one —
        # calling it must not count as a donation site.
        src = """
            import jax

            def build(cache):
                factory = lambda t: cache.wrap(
                    "x", jax.jit(_impl, donate_argnums=(0,))
                )
                prog = factory(4)
                return factory, prog
        """
        r = lint_tree(tmp_path, {"rl/v.py": src})
        assert "use-after-donation" not in rules_hit(r)


# --- rule: host-sync-in-hot-path ------------------------------------------


class TestHostSyncInHotPath:
    def test_item_true_positive_in_hot_module(self, tmp_path):
        src = """
            def loop(metrics):
                return metrics.item()
        """
        r = lint_tree(tmp_path, {"rl/hot.py": src})
        assert "host-sync-in-hot-path" in rules_hit(r)

    def test_same_code_cold_module_is_clean(self, tmp_path):
        src = """
            def loop(metrics):
                return metrics.item()
        """
        r = lint_tree(tmp_path, {"stats/cold.py": src})
        assert "host-sync-in-hot-path" not in rules_hit(r)

    def test_shape_only_transfer(self, tmp_path):
        src = """
            import numpy as np

            def f(batch):
                n = int(np.asarray(batch["v"]).shape[0])
                ok = np.asarray(batch["v"])  # real conversion: not flagged
                return n, ok
        """
        r = lint_tree(tmp_path, {"serving/s.py": src})
        hits = [f for f in r.findings if f.rule == "host-sync-in-hot-path"]
        assert len(hits) == 1
        assert "shape" in hits[0].message

    def test_fragmented_attribute_fetch_and_shallow_negative(self, tmp_path):
        src = """
            import numpy as np

            class S:
                def retire(self, slot):
                    score = float(np.asarray(self.states.score[slot]))
                    local = np.asarray(self.buf)  # depth-1 attr: not flagged
                    return score, local
        """
        r = lint_tree(tmp_path, {"serving/t.py": src})
        hits = [f for f in r.findings if f.rule == "host-sync-in-hot-path"]
        assert len(hits) == 1
        assert "self.states.score" in hits[0].message

    def test_device_get_flagged_and_pragma_allows(self, tmp_path):
        src = """
            import jax

            def fetch(out):
                a = jax.device_get(out)
                b = jax.device_get(out)  # graftlint: allow(host-sync-in-hot-path) the one deliberate fetch
                return a, b
        """
        r = lint_tree(tmp_path, {"mcts/m.py": src})
        hits = [f for f in r.findings if f.rule == "host-sync-in-hot-path"]
        assert len(hits) == 1
        assert hits[0].line == 5
        assert r.suppressed_pragma == 1

    def test_io_callback_true_positive_beacon_callback_sanctioned(
        self, tmp_path
    ):
        # The device-telemetry plane's beacons use jax.debug.callback
        # (unordered, fire-and-forget) — sanctioned in hot programs.
        # io_callback blocks the program on the host round-trip: flagged.
        src = """
            import jax
            from jax.experimental import io_callback

            def wave_body(k, carry):
                jax.debug.callback(lambda i: None, k, ordered=False)
                io_callback(lambda i: i, k, k)
                return carry
        """
        r = lint_tree(tmp_path, {"mcts/beacons.py": src})
        hits = [f for f in r.findings if f.rule == "host-sync-in-hot-path"]
        assert len(hits) == 1
        assert "io_callback" in hits[0].message
        assert hits[0].line == 7

    def test_debug_callback_alone_is_clean(self, tmp_path):
        src = """
            import jax

            def wave_body(k, carry):
                jax.debug.callback(lambda i: None, k, ordered=False)
                return carry
        """
        r = lint_tree(tmp_path, {"rl/beacons.py": src})
        assert "host-sync-in-hot-path" not in rules_hit(r)

    def test_training_loop_and_flywheel_are_hot(self, tmp_path):
        src = """
            def f(x):
                return x.item()
        """
        r = lint_tree(
            tmp_path,
            {"training/loop.py": src, "league/flywheel.py": src,
             "training/setup.py": src},
        )
        hot = [f.path for f in r.findings]
        assert "training/loop.py" in hot
        assert "league/flywheel.py" in hot
        assert "training/setup.py" not in hot


# --- rule: mixed-placement-dispatch ---------------------------------------


MIXED_BAD = """
    import jax
    import numpy as np

    class Runner:
        def __init__(self, cache, fn):
            self._prog = cache.wrap("megastep/t4_k2", fn)

        def bad(self, x, y):
            a = jax.device_put(x)
            b = np.zeros(4)
            return self._prog(a, b)
"""

MIXED_GOOD = """
    import jax
    import numpy as np

    class Runner:
        def __init__(self, cache, fn):
            self._prog = cache.wrap("megastep/t4_k2", fn)

        def good(self, x, y):
            a = jax.device_put(x)
            b = jax.device_put(np.zeros(4))
            return self._prog(a, b)
"""


class TestMixedPlacementDispatch:
    def test_true_positive(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/m.py": MIXED_BAD})
        hits = [
            f for f in r.findings if f.rule == "mixed-placement-dispatch"
        ]
        assert len(hits) == 1
        assert "recompiles" in hits[0].message

    def test_all_committed_is_clean(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/m.py": MIXED_GOOD})
        assert "mixed-placement-dispatch" not in rules_hit(r)


# --- rule: unbracketed-hot-dispatch ---------------------------------------


UNBRACKETED_BAD = """
    class Runner:
        def __init__(self, cache, fn):
            self._mega = cache.wrap("megastep/t4_k2", fn)

        def bad(self, args):
            return self._mega(args)
"""

BRACKETED_GOOD = """
    from ..telemetry.flight import flight_span

    class Runner:
        def __init__(self, cache, fn):
            self._mega = cache.wrap("megastep/t4_k2", fn)
            self._cold = cache.wrap("admit_rows", fn)

        def good_with(self, args):
            with flight_span(self.flight, "megastep", "megastep/t4_k2"):
                return self._mega(args)

        def good_begin(self, args):
            span = self.flight.begin("megastep", "megastep/t4_k2")
            out = self._mega(args)
            span.seal()
            return out

        def cold_family_needs_no_bracket(self, args):
            return self._cold(args)
"""


class TestUnbracketedHotDispatch:
    def test_true_positive(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/d.py": UNBRACKETED_BAD})
        hits = [
            f for f in r.findings if f.rule == "unbracketed-hot-dispatch"
        ]
        assert len(hits) == 1
        assert "'megastep'" in hits[0].message

    def test_bracketed_and_cold_family_clean(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/d.py": BRACKETED_GOOD})
        assert "unbracketed-hot-dispatch" not in rules_hit(r)

    @pytest.mark.parametrize(
        "name", ["self_play_chunk/t64", "learner_step", "serve/b64"]
    )
    def test_all_instrumented_families_covered(self, tmp_path, name):
        src = UNBRACKETED_BAD.replace("megastep/t4_k2", name)
        r = lint_tree(tmp_path, {"rl/d.py": src})
        assert "unbracketed-hot-dispatch" in rules_hit(r)


# --- rule: debug-artifact --------------------------------------------------


class TestDebugArtifact:
    def test_true_positives(self, tmp_path):
        src = """
            import jax

            def f(x):
                jax.debug.print("x={}", x)
                breakpoint()
                return x
        """
        r = lint_tree(tmp_path, {"nn/dbg.py": src})
        hits = [f for f in r.findings if f.rule == "debug-artifact"]
        assert len(hits) == 2

    def test_logger_debug_is_clean(self, tmp_path):
        src = """
            import logging

            logger = logging.getLogger(__name__)

            def f(x):
                logger.debug("x=%s", x)
                return x
        """
        r = lint_tree(tmp_path, {"nn/dbg.py": src})
        assert "debug-artifact" not in rules_hit(r)

    def test_pdb_import(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/p.py": "import pdb\n"})
        assert "debug-artifact" in rules_hit(r)


# --- rule: untracked-rng ---------------------------------------------------


class TestUntrackedRng:
    def test_global_np_random_in_device_module(self, tmp_path):
        src = """
            import numpy as np

            def noise(shape):
                return np.random.randint(0, 4, shape)
        """
        r = lint_tree(tmp_path, {"mcts/r.py": src})
        assert "untracked-rng" in rules_hit(r)

    def test_seeded_generator_and_cold_module_clean(self, tmp_path):
        seeded = """
            import numpy as np

            def gen(seed):
                return np.random.default_rng(seed)
        """
        cold = """
            import numpy as np

            def noise(shape):
                return np.random.randint(0, 4, shape)
        """
        r = lint_tree(tmp_path, {"rl/g.py": seeded, "stats/c.py": cold})
        assert "untracked-rng" not in rules_hit(r)

    def test_stdlib_random_import(self, tmp_path):
        r = lint_tree(tmp_path, {"env/e.py": "import random\n"})
        assert "untracked-rng" in rules_hit(r)


# --- rule: untrapped-exit --------------------------------------------------


class TestUntrappedExit:
    def test_true_positive_hot_path(self, tmp_path):
        src = """
            import sys

            def bail(metrics):
                if metrics["loss"] != metrics["loss"]:
                    sys.exit(1)
        """
        r = lint_tree(tmp_path, {"rl/bail.py": src})
        hits = [f for f in r.findings if f.rule == "untrapped-exit"]
        assert len(hits) == 1
        assert "sys.exit" in hits[0].message

    def test_true_positive_training_os_exit(self, tmp_path):
        src = """
            import os

            def hard_stop():
                os._exit(3)
        """
        r = lint_tree(tmp_path, {"training/stop.py": src})
        assert "untrapped-exit" in rules_hit(r)

    def test_true_negative_cold_module(self, tmp_path):
        # Same code in a cold dir: CLI-ish exits outside the hot path /
        # training loop are not this rule's business.
        src = """
            import sys

            def bail():
                sys.exit(1)
        """
        r = lint_tree(tmp_path, {"stats/report.py": src})
        assert "untrapped-exit" not in rules_hit(r)

    def test_whitelist_sanctioned_exiters(self, tmp_path):
        # The dispatch watchdog (os._exit is the point — the thread that
        # would run shutdown is the wedged one) and the supervisor own
        # process lifecycle; they stay clean even if their dirs are ever
        # promoted into the hot-path set.
        src = """
            import os, sys

            def die():
                os._exit(113)

            def give_up():
                sys.exit(115)
        """
        r = lint_tree(
            tmp_path,
            {"supervise/supervisor.py": src, "telemetry/flight.py": src},
        )
        assert "untrapped-exit" not in rules_hit(r)


# --- engine: pragmas, baseline, exit codes --------------------------------


ONE_PER_RULE = {
    "training/exit.py": """
        import sys

        def f(step):
            sys.exit(1)
    """,
    "rl/donation.py": DONATION_BAD,
    "rl/mixed.py": MIXED_BAD,
    "rl/dispatch.py": UNBRACKETED_BAD,
    "serving/sync.py": """
        def f(x):
            return x.item()
    """,
    "nn/dbg.py": """
        def f(x):
            breakpoint()
            return x
    """,
    "mcts/rng.py": """
        import numpy as np

        def f():
            return np.random.rand(3)
    """,
}


class TestEngine:
    def test_one_violation_per_rule_tree(self, tmp_path):
        r = lint_tree(tmp_path, ONE_PER_RULE)
        assert rules_hit(r) == set(RULE_NAMES)
        assert r.exit_code == 1

    def test_rule_selector(self, tmp_path):
        r = lint_tree(tmp_path, ONE_PER_RULE, rule_names=["debug-artifact"])
        assert rules_hit(r) == {"debug-artifact"}
        assert r.rules == ["debug-artifact"]

    def test_unknown_rule_raises(self, tmp_path):
        with pytest.raises(ValueError, match="unknown rule"):
            lint_tree(tmp_path, ONE_PER_RULE, rule_names=["nope"])

    def test_parse_error_exit_2(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/broken.py": "def f(:\n"})
        assert r.exit_code == 2
        assert r.parse_errors and r.parse_errors[0]["path"] == "rl/broken.py"

    def test_clean_tree_exit_0(self, tmp_path):
        r = lint_tree(tmp_path, {"rl/ok.py": "X = 1\n"})
        assert r.exit_code == 0

    def test_baseline_suppresses_then_stales(self, tmp_path):
        r = lint_tree(tmp_path, {"serving/sync.py": ONE_PER_RULE["serving/sync.py"]})
        assert r.exit_code == 1
        baseline = tmp_path / "baseline.json"
        write_baseline(baseline, r.findings)

        # Same tree + baseline: suppressed, clean.
        r2 = run_lint(tmp_path / "pkg", baseline_path=baseline)
        assert r2.exit_code == 0
        assert r2.suppressed_baseline == 1

        # Finding fixed but baseline kept: the entry is STALE -> dirty.
        (tmp_path / "pkg" / "serving" / "sync.py").write_text(
            "def f(x):\n    return x\n"
        )
        r3 = run_lint(tmp_path / "pkg", baseline_path=baseline)
        assert r3.exit_code == 1
        assert len(r3.stale_baseline) == 1
        assert not r3.findings

    def test_baseline_survives_line_drift(self, tmp_path):
        r = lint_tree(tmp_path, {"serving/sync.py": ONE_PER_RULE["serving/sync.py"]})
        baseline = tmp_path / "baseline.json"
        write_baseline(baseline, r.findings)
        # Prepend lines: finding moves, key (scope+text) does not.
        p = tmp_path / "pkg" / "serving" / "sync.py"
        p.write_text("# header\n# more header\n" + p.read_text())
        r2 = run_lint(tmp_path / "pkg", baseline_path=baseline)
        assert r2.exit_code == 0
        assert r2.suppressed_baseline == 1

    def test_corrupt_baseline_raises(self, tmp_path):
        bad = tmp_path / "b.json"
        bad.write_text("{not json")
        with pytest.raises(ValueError, match="not valid JSON"):
            lint_tree(tmp_path, ONE_PER_RULE, baseline_path=bad)


# --- cli lint --------------------------------------------------------------


class TestCliLint:
    def make_tree(self, tmp_path, files):
        root = tmp_path / "pkg"
        for rel, src in files.items():
            p = root / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        return root

    def test_exit_1_on_seeded_tree_and_json_schema(self, tmp_path, capsys):
        root = self.make_tree(tmp_path, ONE_PER_RULE)
        rc = cli_main(["lint", str(root), "--json"])
        out = json.loads(capsys.readouterr().out.strip())
        assert rc == 1
        assert list(out)[0] == "schema"
        assert out["schema"] == LINT_SCHEMA
        assert {f["rule"] for f in out["findings"]} == set(RULE_NAMES)

    def test_rule_selector_and_exit_codes(self, tmp_path, capsys):
        root = self.make_tree(tmp_path, ONE_PER_RULE)
        assert cli_main(["lint", str(root), "--rule", "debug-artifact"]) == 1
        capsys.readouterr()
        assert cli_main(["lint", str(root), "--rule", "nope"]) == 2
        clean = self.make_tree(tmp_path / "c", {"rl/ok.py": "X = 1\n"})
        capsys.readouterr()
        assert cli_main(["lint", str(clean)]) == 0

    def test_parse_error_exit_2(self, tmp_path, capsys):
        root = self.make_tree(tmp_path, {"rl/broken.py": "def f(:\n"})
        assert cli_main(["lint", str(root)]) == 2

    def test_write_baseline_roundtrip(self, tmp_path, capsys):
        root = self.make_tree(tmp_path, ONE_PER_RULE)
        baseline = tmp_path / "lb.json"
        assert (
            cli_main(
                ["lint", str(root), "--baseline", str(baseline),
                 "--write-baseline"]
            )
            == 0
        )
        capsys.readouterr()
        assert (
            cli_main(["lint", str(root), "--baseline", str(baseline)]) == 0
        )
        out = capsys.readouterr().out
        assert "baselined" in out

    def test_repo_package_lints_clean(self, capsys):
        """THE acceptance gate: the shipped package + checked-in
        baseline produce a clean verdict."""
        rc = cli_main(
            [
                "lint",
                str(REPO / "alphatriangle_tpu"),
                "--baseline",
                str(REPO / "lint_baseline.json"),
            ]
        )
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "clean" in out

    def test_cli_lint_never_imports_jax(self):
        """Subprocess import guard: the lint path (CLI + analysis +
        telemetry.flight's family table) must stay JAX-free, exactly
        like `cli mem`/`cli doctor` — it runs beside a process that
        holds the chip."""
        code = (
            "import builtins, sys\n"
            "real = builtins.__import__\n"
            "def guard(name, *a, **k):\n"
            "    if name == 'jax' or name.startswith('jax.'):\n"
            "        raise AssertionError('cli lint imported ' + name)\n"
            "    return real(name, *a, **k)\n"
            "builtins.__import__ = guard\n"
            "from alphatriangle_tpu.cli import main\n"
            "sys.exit(main(['lint', '--json']))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            cwd=str(REPO),
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
        assert verdict["schema"] == LINT_SCHEMA
        assert verdict["exit_code"] == 0


def test_package_reads_no_bench_knob():
    """The benchmark is `chipbench/` and a run's shapes are its
    preset's: no module of the package names a `BENCH_*` variable, the
    retired measuring script's steering (27 of them once), in code,
    comment or docstring."""
    import re

    knob = re.compile(r"\bBENCH_[A-Z]")
    hits = [
        f"{path.relative_to(REPO)}:{n}"
        for path in sorted((REPO / "alphatriangle_tpu").rglob("*.py"))
        for n, line in enumerate(path.read_text().splitlines(), 1)
        if knob.search(line)
    ]
    assert hits == []
