"""``correct`` at a size a test run holds, on the CPU: the program agrees
with the plain reference; the control and each planted fault do not.

A run is driven with the platform check off and the cell's own limits.
"""
import time

import numpy as np
import pytest

from bench import check, harness
from bench.runners import marl

MARL_CELL = "ic3net-dense.pp-b1024"


@pytest.fixture
def marl_run(tmp_path):
    run = harness.load_run(MARL_CELL, 2**33 + 5, 0.5, False, time.perf_counter())
    run.traffic.update(batch=16)
    run.require_tpu = False
    run.out_dir = str(tmp_path)
    return run


def _break_chunk(monkeypatch, fault):
    import dataclasses
    from repro.marl import train as mt
    real = mt._train_chunk

    def chunk(params, opt, key, plans, start, n, cfg, ecfg, tcfg, env, sched):
        if fault == "half_batch":
            tcfg = dataclasses.replace(tcfg, batch=tcfg.batch // 2)
        out = real(params, opt, key, plans, start, n, cfg, ecfg, tcfg, env, sched)
        if fault == "unchanged":
            return (params, opt) + tuple(out[2:])
        return out
    monkeypatch.setattr(mt, "_train_chunk", chunk)


def test_marl_program_is_correct(marl_run):
    line = marl.run(marl_run)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_marl_fault_is_not_correct(marl_run, monkeypatch, fault):
    _break_chunk(monkeypatch, fault)
    line = marl.run(marl_run)
    assert not line["correct"], line["checks"]


def test_marl_control_is_not_correct(marl_run):
    """At the cell's own batch: the control's gap is a rounding gap, which
    grows with the envs the updates average over."""
    c, t = marl_run.config, harness.load_run(MARL_CELL, 0, 0, False, 0.0).traffic
    ref = marl.reference_observables(c, t, marl_run.seed)
    control = marl.reference_observables(c, t, marl_run.seed, **marl.CONTROL)
    ok, checks = check.verdict(check.training_numbers(control, ref),
                               check.limits_for(MARL_CELL))
    assert not ok, checks


def test_reference_taking_its_own_decisions_changes_nothing(marl_run):
    """The diagnostic path that makes the reference take given actions and
    gates reproduces the reference exactly when given its own."""
    c, t = marl_run.config, marl_run.traffic
    sampled = marl.reference_observables(c, t, marl_run.seed)
    taken = marl.reference_observables(c, t, marl_run.seed,
                                       forced=sampled["taken"])
    assert taken["losses"] == sampled["losses"]
    assert taken["delta"] == sampled["delta"]
    for mine, theirs in zip(taken["taken"], sampled["taken"]):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


def test_look_takes_the_programs_decisions(marl_run):
    """The program's own rollout gives the decisions of each update: the
    reference that takes them agrees with the program at least as well
    as the reference that samples its own."""
    from bench import look
    row = look.look(marl_run.config, marl_run.traffic, marl_run.seed)
    taken, sampled = row["by_update_vs_taken"], row["by_update_vs_sampled"]
    assert taken["delta"] <= max(sampled["delta"], 1e-4)
    assert max(row["loss_gap_taken"]) < 1e-3
    assert len(row["actions_differ"]["decisions"]) == 10
