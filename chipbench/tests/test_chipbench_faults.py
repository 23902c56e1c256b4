"""A run with the timed path broken underneath comes out as not correct.

Each test drives a whole run of a small cell through the harness (the look
for a chip skipped), with one fault planted in the program's own entry
point, and asserts ``correct`` is false; the sound run beside them is
correct.  The faults: a step that returns its state unchanged, half of the
batch left out, and an answer altered where it is produced.  (One chip per
cell: there is no exchange between chips to leave out.)
"""

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "chipbench", "tests", "data")
SEED = 2 ** 31 + 901


def run(cell: str, seconds: float = 0.6) -> dict:
    bench = harness.load_json(os.path.join(DATA, "BENCHMARK.tiny.json"))
    return harness.run_cell(ROOT, cell, SEED, seconds, False,
                            t0=time.perf_counter(), require_chip=False,
                            bench=bench, traffic_dir=DATA)


@pytest.mark.parametrize("cell", ["tiny-whatif", "tiny-replay", "tiny-open"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


# -- what-if: run_scenarios broken --------------------------------------------

def _whatif_fault(monkeypatch, alter):
    from repro.core import scenarios as sc

    real = sc.run_scenarios

    def broken(ss, **kw):
        sim, pred = real(ss, **kw)
        return alter(sim, pred)

    broken._cache_size = real._cache_size
    monkeypatch.setattr(sc, "run_scenarios", broken)


def test_whatif_answer_altered(monkeypatch):
    def alter(sim, pred):
        return sim, dataclasses.replace(pred, power_w=pred.power_w * 1.001)
    _whatif_fault(monkeypatch, alter)
    assert not run("tiny-whatif")["correct"]


def test_whatif_schedule_altered(monkeypatch):
    def alter(sim, pred):
        return dataclasses.replace(
            sim, job_start=sim.job_start.at[:, 0].add(1)), pred
    _whatif_fault(monkeypatch, alter)
    out = run("tiny-whatif")
    assert not out["correct"]
    assert out["checks"]["schedule_mismatch_jobs"]["value"] > 0


def test_whatif_half_batch_left_out(monkeypatch):
    def alter(sim, pred):
        def halve(x):
            return jnp.repeat(x[::2], 2, axis=0)
        return jax.tree.map(halve, sim), jax.tree.map(halve, pred)
    _whatif_fault(monkeypatch, alter)
    assert not run("tiny-whatif")["correct"]


# -- replay: the twin's step broken -------------------------------------------

def _replay_fault(monkeypatch, alter):
    from repro.core import orchestrator
    from repro.core.state import twin_step

    step = jax.jit(twin_step)      # not donating: a fault may keep the state

    def broken(state, telem, sim):
        new, out = step(state, telem, sim)
        return alter(state, new, out)

    broken._cache_size = orchestrator.twin_step_jit._cache_size
    monkeypatch.setattr(orchestrator, "twin_step_jit", broken)


def test_replay_state_unchanged(monkeypatch):
    _replay_fault(monkeypatch, lambda old, new, out: (old, out))
    assert not run("tiny-replay")["correct"]


def test_replay_answer_altered(monkeypatch):
    def alter(old, new, out):
        pred = dataclasses.replace(out.prediction,
                                   power_w=out.prediction.power_w * 1.001)
        return new, dataclasses.replace(out, prediction=pred)
    _replay_fault(monkeypatch, alter)
    assert not run("tiny-replay")["correct"]


# -- service: the fleet step broken -------------------------------------------

def _serve_fault(monkeypatch, alter):
    from repro.core import twin
    from repro.serve import service

    step = jax.jit(twin._fleet_step_masked)   # not donating

    def broken(fleet, telem, sim, active, **kw):
        new, outs = step(fleet, telem, sim, active)
        return alter(fleet, new, outs, active)

    broken._cache_size = twin.fleet_step_masked._cache_size
    monkeypatch.setattr(service, "fleet_step_masked", broken)


def test_serve_state_unchanged(monkeypatch):
    _serve_fault(monkeypatch, lambda old, new, outs, act: (old, outs))
    assert not run("tiny-open", 2.0)["correct"]


def test_serve_half_batch_left_out(monkeypatch):
    from repro.core import twin
    from repro.serve import service

    step = jax.jit(twin._fleet_step_masked, donate_argnums=(0,))

    def broken(fleet, telem, sim, active, **kw):
        keep = np.asarray(active).copy()
        keep[1::2] = False          # the odd half of the lanes never runs
        return step(fleet, telem, sim, jnp.asarray(keep))

    broken._cache_size = twin.fleet_step_masked._cache_size
    monkeypatch.setattr(service, "fleet_step_masked", broken)
    assert not run("tiny-open", 2.0)["correct"]


def test_serve_answer_altered(monkeypatch):
    def alter(old, new, outs, act):
        pred = dataclasses.replace(outs.prediction,
                                   power_w=outs.prediction.power_w * 1.001)
        return new, dataclasses.replace(outs, prediction=pred)
    _serve_fault(monkeypatch, alter)
    assert not run("tiny-open", 2.0)["correct"]
