"""chip_smoke.py refuses to run, and prints no result, without a TPU or
outside a checkout. Run in this process: it already holds the CPU
backend, which is what the script must reject."""
import os
import runpy
import shutil
import sys

import pytest

SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


def _run(path, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", [path])
    monkeypatch.setattr(sys, "path", list(sys.path))
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(path, run_name="__main__")
    out, err = capsys.readouterr()
    return exc.value.code, out, err


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_chip_or_checkout(where, tmp_path,
                                                   monkeypatch, capsys):
    path = SCRIPT
    if where == "alone":
        path = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, path)
    code, out, err = _run(path, monkeypatch, capsys)
    assert code not in (0, None)
    assert '"ok"' not in out
    if where == "checkout":
        assert "platform 'cpu'" in err
    else:
        assert "no src/repro" in err
