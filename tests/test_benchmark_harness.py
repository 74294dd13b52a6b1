"""The benchmark's own tests of its harness arithmetic, under tier-1.

`benchmark/tests/` is in no run the driver makes (its command collects
`tests/`), and two of its cases failed unseen for ten PRs. This file
collects the seven cheap files of it (bounds, counts, the loops, the
manifest, the two trace readers, the profiler's window: 4.5 s in one
process), each case once, under the name `test_<file>__<case>`, and
edits nothing under `benchmark/`: the modules are imported and their
tests and fixtures re-exported here. Parametrised cases keep their ids.

The six costly files stay out (`test_control.py` and the five
`*_rehearse.py`: 400 s, and `tests/test_*_deployment.py` rehearse the
same cells).
"""

import importlib
import types

import pytest
from _pytest.fixtures import FixtureFunctionDefinition

FILES = ("annotations", "bounds", "counts", "loop", "manifest",
         "profiler", "xplane")

#: the cases that fail on the tree as it stands, and why (PERF.md
#: section 7). Both are a `benchmark` issue's to repair; `strict` tells
#: the PR that does to strike its line here.
KNOWN_FAILURES = {
    ("bounds", "test_a_new_cell_needs_no_edit_of_a_file_that_is_there"):
        "since PR 37: NEW names mtu32x4.saturated, a cell the manifest "
        "and benchmark/bounds/cells/ now hold; it should name one the "
        "benchmark lacks (mtu8.burst)",
    ("annotations", "test_annotations_come_back_with_their_stats"):
        "since PR 41: pins the exact list of a traced block's "
        "annotations, and a collection inside it is one now "
        "(rx.pause.gc); it should filter on the names it means",
}

pytest.register_assert_rewrite(
    *(f"benchmark.tests.test_{stem}" for stem in FILES))


def _copy(fn, name):
    """The same test under a new name, with marks of its own: an
    `xfail` added here must not follow the function back into
    `benchmark/tests`' own collection."""
    new = types.FunctionType(fn.__code__, fn.__globals__, name,
                             fn.__defaults__, fn.__closure__)
    new.__dict__.update(fn.__dict__)
    new.__kwdefaults__ = fn.__kwdefaults__
    new.pytestmark = list(getattr(fn, "pytestmark", ()))
    return new


def _collect():
    found = set()
    for stem in FILES:
        mod = importlib.import_module(f"benchmark.tests.test_{stem}")
        for name, obj in list(vars(mod).items()):
            if isinstance(obj, FixtureFunctionDefinition):
                # a fixture is found by the name a test asks for, so it
                # keeps its own; two files asking for one name would
                # shadow each other in silence
                assert name not in globals(), \
                    f"fixture {name!r} of test_{stem}.py collides"
                globals()[name] = obj
            elif name.startswith("test_") and isinstance(
                    obj, types.FunctionType):
                new = f"test_{stem}__{name[len('test_'):]}"
                fn = _copy(obj, new)
                why = KNOWN_FAILURES.get((stem, name))
                if why is not None:
                    found.add((stem, name))
                    fn = pytest.mark.xfail(strict=True, reason=why)(fn)
                globals()[new] = fn
    missing = set(KNOWN_FAILURES) - found
    assert not missing, f"KNOWN_FAILURES names no test: {missing}"


_collect()
