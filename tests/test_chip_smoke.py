"""`chip_smoke.py` refuses to report a result without a chip.

Called in-process (no child touches JAX): the tests run on the CPU, so
the script must exit non-zero and print nothing resembling its final
``{"ok": true, ...}`` line.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_the_cpu(capsys, argv):
    import chip_smoke

    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "needs a TPU" in str(exc.value.code)
