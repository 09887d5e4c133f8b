"""Host->device transfer budget for measurement harnesses.

Every sanctioned upload in the measurement harnesses and the streamed fit
is routed through :func:`charge` / :func:`device_put`, and a configured
budget makes an oversized transfer raise *on the host, before any bytes
move*: a bulk upload that was meant to be synthesized on the device, or a
misconfigured chunk size, fails with a message instead of exhausting the
device.

Two limits, both in bytes:

- ``single``: the per-transfer cap (default 64 MB) — one huge contiguous
  upload. Chunked uploads of the same total pass.
- ``total``: the per-process cap (default 256 MB). Streaming benches that
  legitimately move more declare it via :func:`waive` / a larger env
  budget, so the waiver is visible in the harness source.

Activation: explicitly via :func:`set_budget`, or ambiently via the
``PHOTON_TRANSFER_BUDGET_MB`` / ``PHOTON_TRANSFER_SINGLE_MB`` env vars
(read at first use). With no budget configured every charge is a no-op,
so library users outside measurement harnesses never see this module.

Design note: JAX's own ``jax_transfer_guard`` is not used — on the CPU
backend host->device "transfers" are zero-copy and never fire the guard,
so a CPU dry-run would check nothing, and on any backend it cannot
distinguish a sanctioned chunked upload from one bulk mistake. Byte
accounting at the call sites is deterministic and testable on the CPU.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

__all__ = [
    "TransferBudgetExceeded", "set_budget", "get_budget", "charge",
    "device_put", "waive", "set_activity_hook",
]

# Optional per-charge callback (no args). Measurement harnesses use it as a
# liveness signal: every sanctioned upload — including the margin-ladder
# streams that fire no optimizer-progress callback — proves the run is not
# wedged, so a stall watchdog fed from here cannot falsely kill a live fit
# that is mid-line-search (ADVICE r4).
_activity_hook = None


def set_activity_hook(fn) -> None:
    """Install (or with ``None`` clear) a zero-arg callback fired on every
    budget charge, regardless of whether a budget is configured."""
    global _activity_hook
    _activity_hook = fn


class TransferBudgetExceeded(RuntimeError):
    """A sanctioned upload would exceed the session's transfer budget."""


class _Budget:
    def __init__(self, total: float, single: float, label: str = ""):
        self.total = float(total)
        self.single = float(single)
        self.label = label
        self.spent = 0.0
        self._lock = threading.Lock()

    def charge(self, nbytes: int, what: str = "") -> None:
        nbytes = int(nbytes)
        if nbytes > self.single:
            raise TransferBudgetExceeded(
                f"single host->device transfer of {nbytes/1e6:.1f} MB "
                f"exceeds the per-transfer cap {self.single/1e6:.1f} MB"
                f"{' [' + what + ']' if what else ''} — chunk it, or "
                "synthesize the data on the device")
        with self._lock:
            if self.spent + nbytes > self.total:
                raise TransferBudgetExceeded(
                    f"transfer of {nbytes/1e6:.1f} MB would take this "
                    f"process to {(self.spent + nbytes)/1e6:.1f} MB, over "
                    f"the {self.total/1e6:.1f} MB budget"
                    f"{' [' + what + ']' if what else ''} — synthesize on "
                    "device, or waive explicitly (transfer_budget.waive / "
                    "PHOTON_TRANSFER_BUDGET_MB) if this experiment is "
                    "meant to move bulk data")
            self.spent += nbytes


_budget: Optional[_Budget] = None
_initialized = False


def _ambient() -> Optional[_Budget]:
    """Budget from the environment, if the session runner set one."""
    mb = os.environ.get("PHOTON_TRANSFER_BUDGET_MB")
    if not mb:
        return None
    single = float(os.environ.get("PHOTON_TRANSFER_SINGLE_MB", "64"))
    return _Budget(float(mb) * 1e6, single * 1e6, label="env")


def set_budget(total_mb: Optional[float], single_mb: float = 64.0,
               label: str = "") -> None:
    """Install (or with ``None`` clear) the process transfer budget."""
    global _budget, _initialized
    _initialized = True
    _budget = (None if total_mb is None
               else _Budget(total_mb * 1e6, single_mb * 1e6, label))


def get_budget() -> Optional[_Budget]:
    global _budget, _initialized
    if not _initialized:
        _initialized = True
        _budget = _ambient()
    return _budget


def waive(extra_total_mb: float, reason: str) -> None:
    """Raise the total cap for an experiment that legitimately moves bulk
    data (e.g. a streaming bench). The reason is mandatory so the waiver
    is auditable at the call site; the per-transfer cap stays."""
    b = get_budget()
    if b is not None:
        assert reason, "a transfer-budget waiver needs a reason"
        with b._lock:
            b.total += extra_total_mb * 1e6


def charge(nbytes: int, what: str = "") -> None:
    """Account ``nbytes`` of imminent host->device transfer against the
    budget (no-op when none is configured). Call BEFORE the upload."""
    if _activity_hook is not None:
        _activity_hook()
    b = get_budget()
    if b is not None and nbytes:
        b.charge(nbytes, what)


def device_put(x, sharding=None, what: str = ""):
    """Budget-accounted ``jax.device_put`` for host-resident arrays.

    Charges anything exposing ``nbytes`` that is not already a ``jax.Array``
    — not just ``np.ndarray`` — so chunks built from array-protocol objects
    (memoryviews, mmap-backed arrays, torch CPU tensors) cannot silently
    bypass the budget (ADVICE r4). A committed ``jax.Array`` input is a
    no-op transfer and charges nothing."""
    import jax

    if not isinstance(x, jax.Array):
        nbytes = getattr(x, "nbytes", 0)
        if nbytes:
            charge(int(nbytes), what or "device_put")
    return jax.device_put(x, sharding)
