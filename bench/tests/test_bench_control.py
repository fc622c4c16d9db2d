"""The control: the plain reference put in the program's place at the next
lower precision (float8 operands) must come out not correct under each
cell's limit, where the program itself is within it.  At a width a CPU
test run holds; the chip readings at the cells' own sizes are in PERF.md."""
import pytest

from .tiny import run, tiny_cell


@pytest.mark.parametrize("name", ["granite-3-2b.chat-poisson",
                                  "opt-6.7b.long-decode"])
def test_control_fails_where_the_program_passes(name):
    kw = dict(d=512, layers=4, vocab=2048)
    if name.endswith("long-decode"):
        kw.update(context=512, pages=41, page_size=32, chunk=64)
    cell = tiny_cell(name, **kw)
    limit = cell.limits["max_gap_sigma"]
    res = run(cell, 2 ** 31 + 17, control=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_gap_sigma"]["value"] <= limit
    assert res["control_max_gap_sigma"] > limit
