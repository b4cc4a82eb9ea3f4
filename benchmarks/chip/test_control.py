"""The control fails where the program passes, at a size a test run holds.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/test_control.py

The control is the plain reference put in the program's place and computed
in fp8 (``quant="fp8"``): the tiny configuration of ``test_harness.py`` is
served at its own load on three seeds, and the widest gap of the served
tokens must stay under the limit while the widest gap of the tokens the
control puts first goes over it. On the chip the same readings, at each
cell's own size, set the cells' limits (``calibrate.py readings``).
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE), str(HERE / "family")]

import jax  # noqa: E402

import dense_decoder_program as family  # noqa: E402
import dense_decoder_reference as reference  # noqa: E402
import harness  # noqa: E402
import work  # noqa: E402
from test_harness import MIX, TINY  # noqa: E402


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(work.PEAKS, kind, work.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_separates(seed):
    served, control, counts, _ = harness.readings(TINY, MIX, family, reference,
                                               seed, 1.0, quant="fp8")
    limit = TINY["check"]["logit_gap_limit"]
    assert served.size >= 32 and not any(counts.values())
    assert served.max() < limit < control.max(), (served.max(), control.max())
