"""The benchmark's own CPU tests of the Trinity-Mini family and cell
(``chipbench/tests/test_afmoe.py``, which ``chipbench/tests`` runs by hand),
imported so that tier-1 counts them: ``tests/`` is what tier-1 collects
(PERF.md section 7, "From PR 34" (1)). The file is loaded by its path:
``chipbench/tests`` is no package, and its module names repeat
``tests/``'s."""

import importlib.util
import os

_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "tests", "test_afmoe.py",
)
_spec = importlib.util.spec_from_file_location("chipbench_tests_afmoe", _PATH)
_module = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_module)
globals().update({  # its tests, and the fixtures they name
    name: value for name, value in vars(_module).items()
    if not name.startswith("_")
})


def test_the_manifest_lists_the_cell_and_its_metrics(monkeypatch):
    """``chipbench``'s own test holds PR 35's two metrics to the END of
    ``per_layer``; a later PR appends after them (PR 37: six) and may not
    edit a file the benchmark has (PERF.md section 7). Here the same test
    reads the manifest as PR 35 left it, and what follows is held to be
    appended: entries of other layers' readers, none of PR 35's names."""
    import json

    after = []

    class AsPR35LeftIt:
        @staticmethod
        def load(f):
            manifest = json.load(f)
            names = [m["name"] for m in manifest["per_layer"]]
            cut = names.index("post_norm_ms_per_step") + 1
            after.extend(names[cut:])
            manifest["per_layer"] = manifest["per_layer"][:cut]
            return manifest

    monkeypatch.setattr(_module, "json", AsPR35LeftIt)
    _module.test_the_manifest_lists_the_cell_and_its_metrics()
    assert not {"attention_gate_ms_per_step", "post_norm_ms_per_step"} & set(after)
