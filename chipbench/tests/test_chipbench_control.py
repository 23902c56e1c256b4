"""The control of each cell comes out as not correct.

The control is a whole run of the cell through the harness with the
precision below the configurations' float32 in the program's place: the
program's own bfloat16 read-out for the what-if cells, the reference in
bfloat16 in the twin step's place for the replay and service cells.  Its
run's own check has to come out false by a compared number; the same
stand-in in float32 has to pass.  Here at a small size on the CPU; on the
chip at the cells' own sizes with ``chipbench/control.py``.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "chipbench", "tests", "data")
CELLS = ["tiny-whatif", "tiny-replay", "tiny-open"]
SECONDS = {"tiny-whatif": 0.6, "tiny-replay": 0.6, "tiny-open": 2.0}


def run(name, seed, xp, dtype):
    bench = harness.load_json(os.path.join(DATA, "BENCHMARK.tiny.json"))
    return control.run_control(ROOT, name, seed, SECONDS[name], xp, dtype,
                               require_chip=False, bench=bench,
                               traffic_dir=DATA)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_control_fails_a_limit(name, seed):
    out = run(name, seed, jnp, jnp.bfloat16)  # tracecheck: disable=TC005
    assert not out["correct"], out["checks"]
    assert control.failing(out), out["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_float32_reference_passes(name):
    out = run(name, 7, np, np.float32)
    assert out["correct"], out["checks"]


def test_cells_use_the_limits_of_their_traffic():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        c = harness.resolve(ROOT, w["name"])
        assert c.traffic["limits"], w["name"]
