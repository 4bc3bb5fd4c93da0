"""The control of the check: the reference computed in bfloat16, put in the
program's place, fails the cell's limits while the program passes them.
At the cells' real sizes this runs on the chip through
``bench/calibrate.py``; here at a size a test run holds.

On ``gc-sbm.stream`` the control stands in for the timed updates alone:
each starts from the float32 reference's state before it, and the cold
detection of set-up stays in float32.  At this size bfloat16 changes an
update about once in ten, so that stream runs 30 updates (the chip's
5K-vertex stream differs on every seed within its first 150).
"""

import ml_dtypes
import pytest

from bench import run
from bench.loops import static, stream


def _readings(root, cell, loop_mod, seed, **traffic):
    spec = run.load_cell(cell, root)
    loop = loop_mod.Loop(spec["config"], dict(spec["traffic"], **traffic),
                         seed)
    loop.setup()
    loop.window(0.2)
    loop.release()
    program = {k: max(v) for k, v in loop.check().items()}
    return spec["limits"], program, loop.control(ml_dtypes.bfloat16)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_control_fails_the_limits(root, seed):
    limits, program, control = _readings(root, "graph500.static", static,
                                         seed)
    assert all(program[k] <= limits[k] for k in program)
    assert max(control["mismatch"]) > limits["mismatch"]


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_control_fails_the_stream_updates(root, seed):
    limits, program, control = _readings(root, "gc-sbm.stream", stream,
                                         seed, warmup_batches=30)
    assert all(program[k] <= limits[k] for k in program)
    assert control["mismatch"][0] == 0.0          # the cold detection
    assert max(control["mismatch"][1:]) > limits["mismatch"]
