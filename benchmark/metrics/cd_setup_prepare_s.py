"""The set-up run's ``cd.prepare`` span: the regroup of the entities, the
tables' placement in device memory, the fixed effect's device batch and CSC
view: the part of ``setup_s`` before the first run's first sweep, where the
sweeps' own programs have not yet compiled or loaded. From the program's run
records (the run before the window's)."""

from benchmark import cd_runs


def read(run):
    first = cd_runs.setup_run(run)
    return None if first is None else first["prepare_seconds"]
