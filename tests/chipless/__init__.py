"""Ask the TPU's compiler, without a TPU.

The chip's compiler is installed here and compiles for a described,
unattached ``v5e:2x2``: it refuses what interpret mode and the CPU backend
let through (misaligned kernel slices, too much VMEM, a Mosaic kernel
inside a GSPMD-partitioned jit). A compile that passes is not a chip run;
these tests guard that the main path still *compiles* at real widths.

A file a benchmark cell (``test_judge_batch.py``, ``test_longdoc_batch.py``,
``test_longdoc_wide_reason_batch.py``, ``test_eval_batch_tp4.py``) and one
for the kernels alone (``test_kernels.py``), so that ``--dist loadfile``
hands them to different workers: they were one file while only one process
could load the TPU library, and that file was three quarters of the suite's
wall time. With ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, which the ``topo`` fixture
sets itself, several processes describe the topology side by side.
``tests/conftest.py`` collects this directory first: its files are the
longest of the suite and should be the first a run's workers are given.

The fixtures are in ``conftest.py``; what two files share (the cells'
operands, a module's memo of compiled programs, the readers of compiled
text, the bodies of the cases that several cells run) is in ``cells.py``.
A new kernel's compile alone goes into ``test_kernels.py``, and a cell's
program that holds it into that cell's file. The topology is described
inside a fixture, never at import, and nothing here starts a child process.
"""
