"""The comparison that decides ``correct`` for the lm plane's cells,
driven through the harness at a size the CPU holds: a clean window is
correct; the control (the reference at the precision below the
configuration's, in the program's place) and every fault planted under the
timed path are not."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import chipbench_fault_cases as FC  # noqa: E402

CELLS = sorted(c for c in FC.CELLS if FC.plane_of(c) == "lm")


@pytest.fixture(scope="module", params=CELLS)
def prepared(request):
    import jax
    # the harness sets the configuration's matmul precision process-wide
    old = jax.config.jax_default_matmul_precision
    yield FC.RUN.prepare(request.param, 2**31 + 17, 0.2, need_chip=False,
                         override=FC.SMALL["lm"])
    jax.config.update("jax_default_matmul_precision", old)


@pytest.mark.parametrize("fault", FC.CASES)
def test_harness_catches_each_fault(prepared, fault):
    FC.check_case("lm", prepared, fault)


@pytest.fixture(scope="module", params=CELLS)
def with_fleet(request):
    """A run whose check also compares the fleet the window returns."""
    import jax
    old = jax.config.jax_default_matmul_precision
    small = FC.SMALL["lm"]
    over = {"config": small["config"],
            "traffic": dict(small["traffic"], check={"fleet": True})}
    run = FC.RUN.prepare(request.param, 2**31 + 29, 0.2, need_chip=False,
                         override=over)
    sess = run["session"]
    sess.window()
    yield sess, sess.compare()
    jax.config.update("jax_default_matmul_precision", old)


@pytest.mark.parametrize("fault", [None, "unchanged_losses", "mix_altered"])
def test_fleet_numbers_separate(with_fleet, fault):
    """The fleet's leaf norms agree with the reference on a clean window;
    a dropped update that keeps its losses and a wrong Eq. 4 weighting read
    far above that."""
    sess, clean = with_fleet
    keys = ("change_norm_gap", "moment_norm_gap")
    assert all(clean[k] < 1e-2 for k in keys), clean
    if fault is None:
        return
    with FC.FA.FAULTS["lm"][fault]():
        sess.window()
    out = sess.compare()
    assert out["step_loss_rel_gap"] < 1e-2, out
    assert out["change_norm_gap"] > 10 * clean["change_norm_gap"], out
