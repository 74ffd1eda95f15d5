"""The correctness check's control, at the rehearsal's tiny size: the
reference computed one precision step below the configuration (fp8
weights and matmul inputs, a 4-bit KV tier) must fail the limit that
the program passes, on every seed."""
import pytest

from bench import control, spec
from bench.tests.helpers import FAST, bench_copy


@pytest.mark.parametrize("cell", ("minitron-8b.pp4.generate",
                                  "stablelm-12b.pp4.generate"))
def test_control_fails_where_the_program_passes(tmp_path, cell):
    bj, bd = bench_copy(tmp_path, **FAST)
    limit = spec.load_cell(cell, bj, bd).config["check"]["rehearse_max_logit_gap"]
    seeds = (11, 12, 13)
    got = list(control.readings(cell, seeds, set(seeds), True, bj, bd))
    assert len(got) == 3
    for r in got:
        assert r["tokens"] > 100
        assert r["program"] <= limit < r["control"], r
