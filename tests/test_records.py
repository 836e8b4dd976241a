"""``PERF_LEDGER.jsonl`` (the driver's) and ``PERF.md`` (which cites it) hold
the repo's speed. The files a newcomer reads first state none of their own."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a number before a speed unit; a bare × counts as a multiple of a baseline
#: unless a digit follows it ("dim 512 × 4 layers" is a product)
SPEED = re.compile(
    r"[0-9][\d,.]*\s*(?:k|M)?\s*"
    r"(?:(?:samples|tok|tokens|rows|items)/s(?:/chip)?|TFLOP/s|%[ -]?MFU"
    r"|% of peak|×(?!\s*\d))")


def test_the_pattern_finds_a_speed():
    for text in ("1.27M samples/s/chip", "229.8k tok/s", "35.2% MFU",
                 "277k rows/s", "18.2× the torch-CPU reference"):
        assert SPEED.search(text), text
    assert not SPEED.search("dim 512 × 4 layers, stage×data mesh 2×4")


@pytest.mark.parametrize("name", ["README.md", "PARITY.md"])
def test_states_no_speed_of_its_own(name):
    with open(os.path.join(REPO, name)) as fh:
        found = [m.group(0) for m in SPEED.finditer(fh.read())]
    assert not found, f"{name} states {found}: the ledger holds the numbers"
