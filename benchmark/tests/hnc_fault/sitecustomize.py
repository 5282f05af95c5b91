"""A program-side fault for the hierarchical-namespace cell: the staged
fixpoint step cut to one sweep of at most 2 passes per stage, so a
`parent->view` chain stops after one hop, as a step whose namespace
stage runs too few passes would.

Put this directory first on PYTHONPATH of a harness run
(`PYTHONPATH=benchmark/tests/hnc_fault python3 benchmark/run.py ...`):
Python imports it at start-up in every process, and it plants the fault
in the launcher alone, the process that runs the program.  The run has
to come out not correct.
"""

import os
import sys

if os.path.basename(sys.argv[0] if sys.argv else "") == "launcher.py":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(sys.argv[0]))))
    from spicedb_kubeapi_proxy_tpu.ops import ell

    _init = ell.EllKernelCache.__init__

    def _cut(self, *args, **kwargs):
        _init(self, *args, **kwargs)
        self.num_iters = 1
        if self.stages:
            self.stages = tuple((ranges, min(repeat, 2), wants)
                                for ranges, repeat, wants in self.stages)

    ell.EllKernelCache.__init__ = _cut
