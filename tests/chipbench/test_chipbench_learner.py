"""The learner driver end to end at the tiny board, through the same
function the command line calls (`run.run_cell`), with the look for a
chip skipped by its argument. Then the control and the faults a
training cell can have, each of which has to read as not correct
against the cell's own limits."""

import json

import jax
import jax.numpy as jnp
import pytest
from tiny_cell import tiny_cell

from chipbench import manifest, reference, run
from chipbench.drivers import learner
from chipbench.spans import Spans

SEED = 2**31 + 7


@pytest.fixture(scope="module")
def ran():
    return run.run_cell(
        tiny_cell(), seed=SEED, seconds=0.3, trace=False, require_chip=False
    )


def test_a_run_is_correct_and_prints_the_contracts_line(ran):
    assert ran["correct"] is True and ran["failed"] == 0
    assert ran["attempted"] >= 1
    assert set(ran["metrics"]) == {"learner_steps_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in ran["metrics"].values())
    assert list(ran)[-1] == "compared"
    assert set(ran["compared"]) == set(tiny_cell()["limits"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(ran["device"])
    json.dumps(ran)


def test_the_control_in_the_programs_place_is_not_correct():
    cell = tiny_cell()
    driver = learner.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    ref = driver.reference_group()

    def compared(got):
        read = learner.compare_groups(got, ref)
        return run.compare(
            {name: read[name] for name in learner.COMPARED}, cell["limits"]
        )

    ok, numbers = compared(driver.reference_group(quant=reference.fp8))
    assert not ok, numbers
    ok, numbers = compared(driver.first)
    assert ok, numbers


def _state_unchanged(monkeypatch):
    from alphatriangle_tpu.rl.trainer import Trainer

    real = Trainer.train_steps_from_begin

    def broken(self, buffer, samples):
        kept = jax.tree_util.tree_map(jnp.array, self.state)
        handle = real(self, buffer, samples)
        self.state = kept  # the step's new state is dropped
        return handle

    monkeypatch.setattr(Trainer, "train_steps_from_begin", broken)


def _half_batch(monkeypatch):
    from alphatriangle_tpu.rl.trainer import Trainer

    real = Trainer._stacked_rows_batch

    def broken(rows, weights):
        # The second half of every batch counts for nothing; the mean
        # is taken over the first.
        half = weights.shape[1] // 2
        return real(rows, (2.0 * weights).at[:, half:].set(0.0))

    monkeypatch.setattr(Trainer, "_stacked_rows_batch", staticmethod(broken))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run.run_cell(
        tiny_cell(), seed=SEED, seconds=0.1, trace=False, require_chip=False
    )
    assert out["correct"] is False
    over = [n for n, c in out["compared"].items() if c["value"] > c["limit"]]
    assert over, out["compared"]


def test_the_command_gives_no_result_without_a_chip(capsys):
    code = run.main(
        ["--workload", "flagship-learner", "--seed", "1", "--seconds", "1"]
    )
    assert code == 3
    assert capsys.readouterr().out == ""
