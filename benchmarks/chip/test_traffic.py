"""The traffic generator gives every seed the same work in another order.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/test_traffic.py
"""

from __future__ import annotations

import json
import sys
from collections import Counter
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from traffic import Traffic  # noqa: E402

SEEDS = [1, 7, 2**33 + 5]


def mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("block_s", [2.5, 50.0])
def test_open_loop(block_s):
    m = dict(mix("agent-fixed"), block_s=block_s)
    runs = [Traffic(m, s, 49152).window(50.0) for s in SEEDS]
    per_block = int(m["rate_per_s"] * m["block_s"])
    for reqs in runs:
        due = [r.due for r in reqs]
        assert len(reqs) == 200 and due == sorted(due) and 0 <= due[0] and due[-1] < 50
        blocks = Counter(int(d // m["block_s"]) for d in due)
        assert set(blocks.values()) == {per_block}
        assert {len(r.prompt) for r in reqs} == {128}
        assert all(r.prompt[:96] == reqs[0].prompt[:96] for r in reqs)   # template
    assert runs[0][0].prompt != runs[1][0].prompt
    assert [r.due for r in runs[0]] != [r.due for r in runs[1]]


def test_backlog_is_deterministic():
    m = mix("code-fixed-offline")
    a = Traffic(m, 5, 49152).backlog(16, 16)
    b = Traffic(m, 5, 49152).backlog(0, 32)[16:]
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert {len(r.prompt) for r in a} == {150}
