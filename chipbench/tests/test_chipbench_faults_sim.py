"""The comparison that decides ``correct`` for the sim plane's cells,
driven through the harness at a size the CPU holds: a clean window is
correct; the control (the reference at the precision below the
configuration's, in the program's place) and every fault planted under the
timed path are not."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import chipbench_fault_cases as FC  # noqa: E402

CELLS = sorted(c for c in FC.CELLS if FC.plane_of(c) == "sim")


@pytest.fixture(scope="module", params=CELLS)
def prepared(request):
    import jax
    # the harness sets the configuration's matmul precision process-wide
    old = jax.config.jax_default_matmul_precision
    yield FC.RUN.prepare(request.param, 2**31 + 17, 0.2, need_chip=False,
                         override=FC.SMALL["sim"])
    jax.config.update("jax_default_matmul_precision", old)


@pytest.mark.parametrize("fault", FC.CASES)
def test_harness_catches_each_fault(prepared, fault):
    FC.check_case("sim", prepared, fault)
