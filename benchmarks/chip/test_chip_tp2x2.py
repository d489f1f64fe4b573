"""The (data 2, model 2) cell on four host devices at a reduced size: the
program on that mesh, a sound run that is correct, and each fault that a
mesh of two replicas of two chips can have, each of which the comparison
has to fail.  All of it runs in one child process (``test_chip_fsdp4``'s),
as JAX fixes its device count when it starts."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_chip_fsdp4 import TOY_HELD, run_on_four_devices  # noqa: E402

CELL = "qwen2-l28.tp2x2"
# ``update_doubled`` is caught alike on any mesh (test_chip_harness)
FAULTS = ("state_unchanged", "half_batch", "exchange_left_out",
          "model_exchange_left_out")


@pytest.fixture(scope="module")
def two_by_two():
    return run_on_four_devices(CELL, FAULTS, agreement=False)


def test_a_sound_run_on_a_two_by_two_mesh_is_correct(two_by_two):
    out = two_by_two["sound"]
    assert all(out["checks"][k][0] <= out["checks"][k][1] for k in TOY_HELD), \
        out["checks"]
    assert out["device"]["count"] == 4
    assert two_by_two["program_mesh"] == {"data": 2, "model": 2}
    # the reference shards its state and its batch as in the fsdp4 cell
    assert two_by_two["shards"] == {"batch_rows": [1, 1, 1, 1],
                                    "batch_devices": 4,
                                    "weights_by_last_dim": [True]}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_on_a_two_by_two_mesh_makes_the_run_incorrect(two_by_two,
                                                              fault):
    out = two_by_two[fault]
    assert not out["correct"], out["checks"]
    # each fault moves the first gradient itself, far past its limit
    value, limit = out["checks"]["grad_diff"]
    assert value > 5 * limit, out["checks"]
