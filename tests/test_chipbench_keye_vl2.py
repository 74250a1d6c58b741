"""The benchmark's own CPU tests of the Keye-VL-2.0-30B-A3B family and cell
(``chipbench/tests/test_keye_vl2.py``, which ``chipbench/tests`` runs by
hand), imported so that tier-1 counts them: ``tests/`` is what tier-1
collects. The file is loaded by its path: ``chipbench/tests`` is no package,
and its module names repeat ``tests/``'s."""

import importlib.util
import os

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "tests", "test_keye_vl2.py",
)
_spec = importlib.util.spec_from_file_location("chipbench_tests_keye_vl2", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({  # its tests, and the fixtures they name
    name: value for name, value in vars(_module).items()
    if not name.startswith("_")
})
