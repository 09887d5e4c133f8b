"""The program's own records of the GLMix cell's runs and of the fetches
that block its host, for the readers of ``cd_syncs_per_sweep``,
``cd_sweep_host_ms``, ``cd_prepare_ms``, ``cd_finish_ms`` and
``cd_setup_prepare_s``. A run record (``TrainingMetrics.run_records``, the
process that ran the window) is one ``CoordinateDescent.run``: the window's
runs are its last ``len(pieces)``, the set-up run the one before them.
Nothing where the program keeps no such record or count."""

from __future__ import annotations

from benchmark import flops_bytes_game


def _records():
    from photon_ml_tpu.obs.metrics import training_metrics

    read = getattr(training_metrics(), "run_records", None)
    return read() if read is not None else []


def window_runs(run):
    n = len(run.window.get("pieces") or ())
    records = _records()
    return records[-n:] if n and len(records) >= n else None


def setup_run(run):
    n = len(run.window.get("pieces") or ())
    records = _records()
    return records[-n - 1] if n and len(records) > n else None


def synced_sweeps(run):
    """The window's sweep records, where they count their fetches."""
    sweeps = flops_bytes_game.window_sweeps(run)
    return sweeps if sweeps and "syncs" in sweeps[0] else None
