"""Roofline guards: the benchmarks refuse to publish physically
impossible numbers."""

import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARKS = os.path.join(REPO, "benchmarks")

# load by file path (not sys.path) so the benchmarks dir's module names
# (_bootstrap, ladder, ...) can't shadow anything for later tests
import importlib.util  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "_roofline", os.path.join(BENCHMARKS, "_roofline.py")
)
_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_roofline)
VIOLATION_PREFIX, guard = _roofline.VIOLATION_PREFIX, _roofline.guard


class TestGuard:
    def test_under_bound_is_noop(self, capsys):
        guard("x", 10.0, "img/s", 100.0, "detail")
        assert capsys.readouterr().out == ""

    def test_over_bound_exits_5(self, capsys):
        with pytest.raises(SystemExit) as ei:
            guard("decode", 2.5e6, "tok/s", 3.3e4, "weight-read bound")
        assert ei.value.code == 5
        out = capsys.readouterr().out
        assert out.startswith(VIOLATION_PREFIX)
        assert "decode" in out and "weight-read bound" in out

    def test_soft_raises_runtime_error(self):
        # ladder's per-config isolation catches Exception, not SystemExit
        with pytest.raises(RuntimeError, match=VIOLATION_PREFIX):
            guard("cfg4", 2.0, "tok/s", 1.0, "d", soft=True)
