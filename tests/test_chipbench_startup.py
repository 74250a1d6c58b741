"""The benchmark's own CPU rehearsal of the start-up ledger's six readers
(``chipbench/tests/test_startup_ledger.py``, which ``chipbench/tests`` runs by
hand), imported so that tier-1 counts it: ``tests/`` is what tier-1 collects.
The file is loaded by its path: ``chipbench/tests`` is no package, and its
module names repeat ``tests/``'s."""

import importlib.util
import os

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "tests", "test_startup_ledger.py",
)
_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_startup_ledger", _PATH
)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({  # its tests, and the fixtures they name
    name: value for name, value in vars(_module).items()
    if not name.startswith("_")
})
