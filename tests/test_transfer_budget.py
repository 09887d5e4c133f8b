"""Transfer-budget guard (utils.transfer_budget): an oversized
host->device upload raises on the host before any bytes move. These run
on the CPU mesh — the budget is backend-independent byte accounting, so
a CPU run exercises the same enforcement a chip run relies on."""

import numpy as np
import jax.numpy as jnp
import pytest

from photon_ml_tpu.utils import transfer_budget as tb


@pytest.fixture(autouse=True)
def _clean_budget():
    tb.set_budget(None)
    yield
    tb.set_budget(None)


def test_no_budget_is_noop():
    tb.charge(10**12)  # would exceed any real budget


def test_single_transfer_cap():
    tb.set_budget(total_mb=1000.0, single_mb=1.0)
    tb.charge(900_000, "ok piece")
    with pytest.raises(tb.TransferBudgetExceeded, match="per-transfer cap"):
        tb.charge(2_000_000, "bulk")


def test_total_budget_accumulates():
    tb.set_budget(total_mb=1.0, single_mb=1.0)
    for _ in range(2):
        tb.charge(400_000)
    with pytest.raises(tb.TransferBudgetExceeded, match="over the"):
        tb.charge(400_000)
    # a failed charge must not have been added
    assert tb.get_budget().spent == 800_000


def test_waive_raises_total_but_not_single():
    tb.set_budget(total_mb=1.0, single_mb=1.0)
    tb.waive(10.0, reason="streaming bench moves bulk data by design")
    tb.charge(900_000)
    tb.charge(900_000)  # over the original total, under the waived one
    with pytest.raises(tb.TransferBudgetExceeded, match="per-transfer cap"):
        tb.charge(2_000_000)


def test_env_activation(monkeypatch):
    monkeypatch.setenv("PHOTON_TRANSFER_BUDGET_MB", "1")
    monkeypatch.setenv("PHOTON_TRANSFER_SINGLE_MB", "0.5")
    tb.set_budget(None)
    tb._initialized = False  # force re-read of the env
    with pytest.raises(tb.TransferBudgetExceeded):
        tb.charge(600_000)


def test_device_put_charges_numpy_only():
    tb.set_budget(total_mb=1.0, single_mb=1.0)
    tb.device_put(np.zeros(1000, np.float32))
    assert tb.get_budget().spent == 4000
    # already-on-device arrays are not host->device transfers
    tb.device_put(jnp.zeros(1000))
    assert tb.get_budget().spent == 4000


def test_streamed_fit_respects_budget():
    """fit_streaming's chunk uploads are budget-accounted: a budget too
    small for even one chunk aborts on the host before any transfer."""
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.parallel.streaming import HostChunk, fit_streaming

    rng = np.random.default_rng(0)
    n, k, dim = 256, 4, 64
    chunks = [HostChunk(rng.integers(0, dim, (n, k)).astype(np.int32),
                        None,
                        rng.integers(0, 2, n).astype(np.float32),
                        np.zeros(n, np.float32), np.ones(n, np.float32))]
    obj = make_objective("logistic")
    cfg = OptimizerConfig(max_iters=2, tolerance=0.0)

    tb.set_budget(total_mb=1e-6, single_mb=64.0)
    with pytest.raises(tb.TransferBudgetExceeded):
        fit_streaming(obj, chunks, dim, config=cfg)

    # a sane budget passes and records real bytes moved
    tb.set_budget(total_mb=64.0, single_mb=64.0)
    res = fit_streaming(obj, chunks, dim, config=cfg)
    assert int(res.iterations) == 2
    assert tb.get_budget().spent > 0
