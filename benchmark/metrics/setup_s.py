"""Process start to the window's start, less the seconds `jax.devices()`
took to bring the TPU runtime up (`run.setup_phases.chip_acquire_s`): imports,
data from the seed, the program's precomputed views, compile or cache load,
one warm-up. The runtime's start-up wanders from 8 to 19 s between
processes of one code and is no work of the program's or the benchmark's."""


def read(run):
    return run.setup_s
