"""Tiny sizes of the cells added after ``tiny.py`` was written, and the
one case of an older test that cannot apply to them.

``test_bench_harness.py::test_reference_weights_have_the_programs_shapes``
builds every configuration's program through ``round_cell.model_config``,
the dense decoder; the hybrid configuration is built by
``hybrid_round_cell.model_config``, and ``test_bench_hybrid.py::
test_reference_builds_every_program_weight`` checks the same property for
it.  That case is deselected, not run.
"""
from bench.tests import tiny_hybrid

tiny_hybrid.register()

NOT_DENSE = ("test_bench_harness.py::test_reference_weights_have_the_programs_shapes"
             "[granite-4.0-h-small]")


def pytest_collection_modifyitems(config, items):
    gone = [it for it in items if it.nodeid.endswith(NOT_DENSE)]
    if gone:
        items[:] = [it for it in items if it not in gone]
        config.hook.pytest_deselected(items=gone)
