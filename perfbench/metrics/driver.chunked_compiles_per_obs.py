"""Backend compiles per observation in the window of a chunked-path
cell: ``driver.compiles_per_obs``'s reading (the program's
``jit.backend_compiles`` counter around each CLI call, which counts
programs read from the persistent compile cache too), by its reader."""

import os

from perfbench import run as harness


def read(run):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return harness.read_metric("driver.compiles_per_obs", run, root)
